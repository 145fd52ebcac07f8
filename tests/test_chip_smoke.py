"""chip_smoke.py off the chip: it must refuse to report success without a
TPU, and its phases must run end to end at smoke sizes (kernels in
interpret mode) so the control flow is rehearsed before any chip run."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, run_in_cpu_mesh
from repro.configs import get_smoke_config

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_chip_smoke_refuses_without_a_tpu(where, tmp_path):
    script, cwd = SCRIPT, REPO
    if where == "script_alone":
        script = str(shutil.copy(SCRIPT, tmp_path))
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=cwd, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_chip_smoke_phases_run_on_cpu(tmp_path):
    cs = _load()
    pde = cs.pde_train(get_smoke_config("flare_pde"), batch=2, points=64,
                       ckpt_root=str(tmp_path))
    assert pde["loss_finite"] and len(pde["losses"]) == 3
    assert pde["compile_s"] > 0 and pde["steady_s"] > 0
    # interpret-mode kernel against the f32 oracle: rounding only
    assert pde["ref"]["finite"]
    assert max(pde["ref"]["errors"].values()) < 1e-4
    assert not os.listdir(tmp_path)  # the checkpoint directory is gone

    kw = dict(requests=3, new_tokens=4)
    srv = cs.serve(get_smoke_config("qwen2_1_5b"), slots=2, capacity=64,
                   pool_tokens=256, prompt_lo=8, prompt_hi=24, min_bucket=8,
                   **kw)
    assert srv["decode_backend"].startswith("paged")
    assert srv["decode_compiles_serving"] == 0
    assert srv["host_syncs_per_step"] == 0.0
    assert srv["tokens"] == [kw["new_tokens"]] * kw["requests"]
    # off the chip the routes are bit-identical
    assert srv["logits_rel_l2"] == 0.0

    # interpret mode compiles no Mosaic kernel, so the chip checks refuse
    assert not pde["kernel_in_step"] and not srv["kernel_in_step"]
    with pytest.raises(cs.SmokeFailure):
        cs.check_pde(pde)
    with pytest.raises(cs.SmokeFailure, match="tpu_custom_call"):
        cs.check_serve(srv, **kw)


def test_chip_smoke_mesh_phases_run_on_four_cpu_devices():
    run_in_cpu_mesh(rf"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)
from repro.configs import get_smoke_config
from repro.distributed.sharding import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
tr = cs.mesh_train(get_smoke_config("flare_pde"), mesh, batch=2, points=64)
assert tr["backend"] == "packed_shard"
# interpret mode: the split token sum differs from one launch by rounding
assert max(tr["errors"].values()) < 1e-4, tr["errors"]
kw = dict(requests=4, new_tokens=4)
sv = cs.mesh_serve(get_smoke_config("qwen2_1_5b"), mesh, slots=4,
                   capacity=64, pool_tokens=256, prompt_lo=8, prompt_hi=24,
                   min_bucket=8, **kw)
cs.check_mesh_serve(sv, **kw)
print("PASS")
""")
