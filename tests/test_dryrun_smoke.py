"""Fast dry-run path smoke: one reduced-depth cell lowered + compiled on the
512-device production mesh in a subprocess (the full 40-cell × 2-mesh sweep
runs via `python -m repro.launch.dryrun --all`; its results land in
results/dryrun and EXPERIMENTS.md §Dry-run)."""
import json
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import json
    from repro.launch.dryrun import lower_cell

    rec = lower_cell("gemma-7b", "decode_32k", multi_pod=True,
                     config_overrides={"n_layers": 4})
    out = {
        "ok": "roofline" in rec,
        "n_devices": rec.get("n_devices"),
        "bottleneck": rec.get("roofline", {}).get("bottleneck"),
        "flops": rec.get("roofline", {}).get("flops_per_device", 0) > 0,
        "wire": rec.get("roofline", {}).get("wire_bytes_per_device", 0) >= 0,
        "mem": rec.get("memory", {}).get("peak_est_bytes", 0) > 0,
    }
    print(json.dumps(out))
""")


def test_dryrun_cell_multi_pod_reduced_depth():
    # a virtual CPU mesh: the child never takes an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["n_devices"] == 512
    assert res["flops"] and res["wire"] and res["mem"]
