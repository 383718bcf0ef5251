"""End-to-end driver: train a ~100M-parameter qwen2.5-family model for a few
hundred steps on the synthetic pipeline, with checkpointing and a scheduled
mid-run failure + restart — the fault-tolerance path, end to end.

    PYTHONPATH=src python examples/train_tiny_lm.py
"""
import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.train import main as train_main


def main():
    enable_compile_cache()
    cfg = get_config("qwen2.5-32b", reduced=True).replace(
        n_layers=8, d_model=512, n_heads=8, n_kv_heads=4, head_dim=64,
        d_ff=2048, vocab=8192)
    # ~100M params: verify
    from repro.models import build_model
    import numpy as np
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        build_model(cfg).abstract()))
    print(f"model: {n / 1e6:.1f}M params")

    # drive through the production train driver (with a failure at step 120)
    import repro.configs as configs
    configs._MODULES["tiny-100m"] = type(
        "M", (), {"FULL": cfg, "REDUCED": cfg})
    summary = train_main([
        "--arch", "tiny-100m", "--steps", "300", "--batch", "16",
        "--seq", "256", "--lr", "1e-3", "--fail-at", "120",
        "--save-every", "50", "--ckpt-dir", "/tmp/tiny100m_ckpt",
    ])
    assert summary["last_loss"] < summary["first_loss"] * 0.7, summary
    print("loss dropped:", summary["first_loss"], "->", summary["last_loss"],
          f"(restarts={summary['restarts']}, lost={summary['lost_steps']})")


if __name__ == "__main__":
    main()
