"""Distribution-layer tests: sharding-rule fallbacks (host-side logic) and
multi-device semantics (pipeline parallelism, mesh building, dry-run lower)
exercised in subprocesses with forced host device counts."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import PRESETS


def _fake_mesh(shape, axes):
    """Rules only consult mesh.shape / axis_names — a stub suffices."""
    class M:
        axis_names = axes
        def __init__(self):
            self.shape = dict(zip(axes, shape))
    return M()


def _rules(preset="train", shape=(16, 16), axes=("data", "model")):
    from repro.runtime.sharding import Rules
    return Rules(mesh=_fake_mesh(shape, axes), table=dict(PRESETS[preset]))


def test_rules_basic_2d_weight():
    r = _rules()
    assert r.spec((5120, 5120), ("embed", "heads")) == P("data", "model")


def test_rules_divisibility_fallback():
    r = _rules()
    # kv_heads=8 cannot shard over model=16 -> replicated dim
    assert r.spec((4096, 8, 128), (None, "kv_heads", None)) == P(None, None, None)
    # but the flattened 1024 column dim can
    assert r.spec((4096, 1024), ("embed", "kv_heads")) == P("data", "model")


def test_rules_no_axis_reuse():
    r = _rules()
    # vocab and seq_sp both want "model": the later dim must fall back
    spec = r.spec((256, 4096, 152064), ("batch", "seq_sp", "vocab"))
    assert spec == P("data", "model", None)


def test_rules_multi_axis_batch():
    r = _rules(shape=(2, 16, 16), axes=("pod", "data", "model"))
    assert r.spec((256, 4096), ("batch", None)) == P(("pod", "data"), None)


def test_rules_fsdp_preset_two_axis_embed():
    r = _rules(preset="fsdp")
    assert r.spec((3072, 4096), ("embed", "heads")) == P(("data", "model"), None)


def test_rules_none_mesh_noop():
    from repro.runtime.sharding import make_rules
    r = make_rules(None)
    x = np.ones((4, 4))
    assert r(x, ("batch", None)) is x


_PP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, json
    from repro.launch.mesh import make_mesh_shape
    from repro.runtime.pp import gpipe, bubble_fraction

    S, M, mb, d = 4, 8, 2, 16
    mesh = make_mesh_shape((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, d, d)) * 0.3

    def stage(w, x):
        return jnp.tanh(x @ w)

    xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    got = gpipe(stage, ws, xs, mesh=mesh, axis="stage")

    ref = xs
    for s in range(S):
        ref = jax.vmap(lambda x: stage(ws[s], x))(ref)

    ok = bool(jnp.allclose(got, ref, atol=1e-5))
    print(json.dumps({"ok": ok, "bubble": bubble_fraction(M, S)}))
""")


def test_gpipe_matches_sequential():
    # a virtual CPU mesh: the child never takes an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _PP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"], res
    assert res["bubble"] == pytest.approx(3 / 11)


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax, json
    from repro.launch.mesh import make_production_mesh
    m1 = make_production_mesh()
    m2 = make_production_mesh(multi_pod=True)
    print(json.dumps({"single": dict(m1.shape), "multi": dict(m2.shape)}))
""")


def test_production_meshes_build():
    # a virtual CPU mesh: the child never takes an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["single"] == {"data": 16, "model": 16}
    assert res["multi"] == {"pod": 2, "data": 16, "model": 16}
