"""Pin the (non-)reproducibility of the GSPMD sp-axis conv-grad bug.

Round 1 documented a workaround in training.py: annotating the conv input's
H axis with the "sp" mesh axis under jit allegedly produced wrong conv
*weight* gradients, so sp-training was routed through the explicit
shard_map + ppermute halo path instead.

Round-2 investigation (scripts/gspmd_conv_grad_repro.py) could NOT reproduce
the bug on the CPU backend with jax==0.9.0 — not with a minimal conv, not
with the full Blocks 1-2 model at H=227, not with remat, not with a dp x sp
mesh. These tests pin that finding:

- test_gspmd_sp_annotation_grads_correct_on_cpu PASSES = GSPMD grads are
  correct on this backend/build. If it ever FAILS, the round-1 bug has
  appeared (e.g. after a JAX upgrade) and the shard_map routing in
  training.py is load-bearing for numerics, not just for design.
- The shard_map halo path remains the default for sp-training regardless:
  it is the framework's explicit-collectives design (the reference's MPI
  halo analogue), and the GSPMD behavior on the TPU backend — where the
  round-1 observation may have originated — is still unverified.

Run the paired script on a real TPU to settle the backend question:
    python scripts/gspmd_conv_grad_repro.py
"""

import importlib.util
import os



def _load_repro():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "gspmd_conv_grad_repro.py",
    )
    spec = importlib.util.spec_from_file_location("gspmd_conv_grad_repro", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gspmd_sp_annotation_grads_correct_on_cpu():
    # conftest.py already forces the 8-device virtual CPU mesh; do NOT call
    # the script's force_cpu() here (backend is already initialized).
    mod = _load_repro()
    wdiff, bdiff, ldiff = mod.grad_mismatch(n_shards=4)
    assert ldiff < 1e-4, f"forward loss diverged under sp annotation: {ldiff}"
    assert bdiff < 1e-4, f"bias grads diverged under sp annotation: {bdiff}"
    assert wdiff < 1e-3, (
        f"conv weight grads diverged under sp annotation (max|diff|={wdiff}): "
        "the round-1 GSPMD bug is BACK — the shard_map routing in "
        "training.py (x_spec) is now numerically load-bearing"
    )


def test_repro_script_exit_code_contract():
    """Drive the script as a CLI: rc 1 = bug absent, rc 0 = bug present."""
    import subprocess
    import sys

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "gspmd_conv_grad_repro.py",
    )
    proc = subprocess.run(
        [sys.executable, path], capture_output=True, text=True, timeout=300
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "bug NOT reproduced" in proc.stdout
