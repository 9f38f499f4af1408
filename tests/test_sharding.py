"""Sharding rules + a miniature end-to-end dry-run in a subprocess
(the subprocess gets its own XLA_FLAGS with fake devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import jax

from repro.sharding import fit_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh_1dev():
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    return Mesh(dev, ("node", "fsdp", "model"))


def test_fit_spec_drops_nondivisible():
    mesh_dims = {"node": 4, "fsdp": 2, "model": 8}

    class FakeMesh:
        axis_names = tuple(mesh_dims)
        devices = np.empty(tuple(mesh_dims.values()))

    spec = fit_spec(("fsdp", "model"), (64, 128), FakeMesh())
    assert spec == P("fsdp", "model")
    spec = fit_spec(("fsdp", "model"), (63, 128), FakeMesh())
    assert spec == P(None, "model")
    # padding for extra leading dims
    spec = fit_spec(("fsdp", "model"), (10, 64, 128), FakeMesh())
    assert spec == P(None, "fsdp", "model")
    # duplicate axis collapses to one use
    spec = fit_spec(("model", "model"), (64, 64), FakeMesh())
    assert spec == P("model", None)


def test_fit_spec_fallback_candidates():
    class FakeMesh:
        axis_names = ("node", "fsdp", "model")
        devices = np.empty((2, 1, 16))

    # kv=8 cannot shard over model=16 -> falls to head_dim 128
    spec = fit_spec((("model",), ("model",)), (8, 128), FakeMesh())
    assert spec == P(None, "model")


DRYRUN_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import numpy as np
    from jax.sharding import AxisType, Mesh
    import repro.launch.dryrun as dr
    import repro.launch.mesh as lm

    # shrink the production mesh so the test runs fast on 8 fake devices
    def tiny_prod(*, multi_pod=False):
        shape = (2, 2, 2) if multi_pod else (4, 2)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

    def tiny_logical(cfg, *, multi_pod=False, production=None):
        prod = production or tiny_prod(multi_pod=multi_pod)
        devs = np.asarray(prod.devices).reshape(-1)
        return Mesh(devs.reshape(2, 2, 2), ("node", "fsdp", "model"),
                    axis_types=(AxisType.Auto,) * 3)

    lm.make_production_mesh = tiny_prod
    dr.make_production_mesh = tiny_prod
    dr.make_logical_mesh = tiny_logical

    # reduced shapes so the smoke config compiles in seconds
    from repro.configs.shapes import InputShape
    dr.INPUT_SHAPES = {
        "train_4k": InputShape("train_4k", 64, 8, "train"),
        "decode_32k": InputShape("decode_32k", 128, 8, "decode"),
        "prefill_32k": InputShape("prefill_32k", 64, 4, "prefill"),
    }
    from repro.configs import get_config as real_get
    dr.get_config = lambda name, variant="full": real_get(name, "smoke")

    out = {}
    for shape in ["train_4k", "prefill_32k", "decode_32k"]:
        for mesh in ["single", "multi"]:
            rec = dr.run_combo("ARCH", shape, mesh, remat=False)
            out[f"{shape}|{mesh}"] = {
                "flops": rec["flops_per_device"],
                "coll": rec["collective_bytes_total"],
                "layout": rec["layout"],
            }
    print("RESULT" + json.dumps(out))
    """
)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "zamba2-1.2b", "deepseek-v2-lite-16b"])
def test_mini_dryrun_subprocess(arch):
    """Every step kind lowers+compiles on an 8-device (node,fsdp,model) mesh,
    single- and multi-pod, for a dense, a hybrid and an MoE/MLA arch."""
    code = DRYRUN_SNIPPET.replace("ARCH", arch)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    payload = [l for l in res.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(payload[len("RESULT"):])
    assert len(out) == 6
    for key, rec in out.items():
        assert rec["flops"] > 0, key
        if "train" in key:
            assert rec["coll"] > 0, f"train step must gossip: {key}"


def test_production_mesh_shapes():
    """make_production_mesh contract (verified abstractly on device counts)."""
    from repro.launch.mesh import fsdp_degree
    from repro.configs import get_config

    # big archs get fsdp > 1, small archs fsdp == 1
    assert fsdp_degree(get_config("stablelm-1.6b"), 256) == 1
    assert fsdp_degree(get_config("yi-34b"), 256) > 1
    assert fsdp_degree(get_config("deepseek-v2-236b"), 256) >= 8
    # node count stays >= 2
    for arch in ("yi-34b", "deepseek-v2-236b"):
        f = fsdp_degree(get_config(arch), 256)
        assert 256 // (f * 16) >= 2
