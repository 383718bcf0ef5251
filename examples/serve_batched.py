"""Serve a small model with batched requests: prefill + greedy decode over
KV caches, across three different architecture families (GQA cache, MLA
compressed cache, SSM recurrent state).

    PYTHONPATH=src python examples/serve_batched.py
"""
from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


def main():
    enable_compile_cache()
    for arch in ("qwen2.5-32b", "deepseek-v3-671b", "mamba2-2.7b"):
        print(f"--- {arch} (reduced config) ---")
        serve_main(["--arch", arch, "--batch", "4",
                    "--prompt-len", "48", "--gen", "16"])


if __name__ == "__main__":
    main()
