"""chip_smoke.py's control flow, and what keeps a failure from hiding,
guarded on the CPU on every PR (the chip run itself is the builder's and
the driver's: ``python chip_smoke.py`` through the chip tool).

The phase functions are the script's own, called at a tiny size.  What a
chip decides there — which device holds the parameters, whether the
kernels are in the program — is steered from here, through the kernels'
existing knobs and the arguments the phases take, never through an
option of the script.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

LM = dict(num_classes=64, num_layers=2, d_model=32, num_heads=2,
          seq_len=32, dtype="float32")
TRAIN = dict(batch=2, steps=4,
             kernels=("layernorm_fused", "layernorm_fused_bwd"))
SERVE = dict(capacity=4, block_size=4, num_blocks=32, chunk_tokens=8,
             prompt_lens=(3, 9, 14), max_new_tokens=4,
             kernels=("paged_decode_attend", "paged_chunk_prefill_attend"),
             impl="pallas")


@pytest.fixture
def kernels_forced(monkeypatch):
    """On a chip ``auto`` picks the compiled kernels.  Here the knobs
    force the same kernels (interpret mode) so the phases' "the kernel
    is in the program, and nothing fell back" checks see what they would
    see there; the flash pair has no knob and no interpret path: its
    choice answers no here without booking the fallback it would."""
    from mxnet_tpu.ops import nn
    monkeypatch.setenv("MXNET_LN_IMPL", "pallas")
    monkeypatch.setenv("MXNET_PAGED_ATTN_IMPL", "pallas")
    monkeypatch.setattr(nn, "_use_flash_attention", lambda *a, **k: False)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_train_and_serve_phases_tiny(kernels_forced, capsys):
    ctx = mx.cpu(0)
    params = chip_smoke.phase_train_lm(ctx, LM, TRAIN)
    chip_smoke.phase_train_resnet(ctx, dict(
        num_layers=18, image_shape=(3, 32, 32), batch=4, steps=3,
        dtype="float32", layout="NHWC"))
    chip_smoke.phase_serve_lm(ctx, params, LM, SERVE)
    train, resnet, serve = _lines(capsys)
    assert train["phase"] == "train-lm"
    assert train["losses"][-1] < train["losses"][0]
    assert train["pallas_kernels_built"]["layernorm_fused_bwd"] > 0
    assert resnet["phase"] == "train-resnet" and len(resnet["losses"]) == 3
    assert serve["phase"] == "serve-lm" and serve["attn_impl"] == "pallas"
    assert serve["engine_vs_xla"]["steps_compared"] == 12
    assert serve["dispatches_per_step"] == 1.0
    assert serve["steady_state_retraces"] == 0


def test_multichip_phase_tiny(kernels_forced, capsys):
    """--chips 4's phase on four of the suite's virtual CPU devices."""
    chip_smoke.phase_multichip(LM, TRAIN, SERVE, ctx_of=mx.cpu)
    fit, decode = _lines(capsys)
    assert fit["phase"] == "multichip-fit"
    assert sorted(fit["param_bytes_per_device"]) == ["0", "1", "2", "3"]
    np.testing.assert_allclose(fit["mesh_losses"], fit["one_chip_losses"],
                               rtol=2e-2)
    assert decode["phase"] == "multichip-decode"
    assert len(decode["kv_cache_bytes_per_device"]) == 2
    assert mx.sharding.get_mesh() is None          # the phase cleans up


def test_a_failing_check_stops_the_script(kernels_forced):
    """No phase outlives a failed check: asking for a kernel that was
    not built is a SystemExit, not a line further down."""
    with pytest.raises(SystemExit, match="flash_attention was not built"):
        chip_smoke.phase_train_lm(
            mx.cpu(0), LM, dict(TRAIN, kernels=("flash_attention",)))


def test_streams_differ_only_at_near_ties():
    ref = [([3, 5], [np.array([0., 1., 2., 9.]), np.array([0., 1., 2., 3.])])]
    flip = [([3, 4], [np.array([0., 1., 2., 9.]), np.array([0., 1., 2., 3.])])]
    with pytest.raises(SystemExit, match="top-2 margin"):
        chip_smoke._compare_streams(ref, flip, "t", tol=0.05)
    tie = [([3, 5], [np.array([0., 1., 2., 9.]),
                     np.array([0., 1., 2.95, 3.])])]
    got = [([3, 2], [np.array([0., 1., 2., 9.]),
                     np.array([0., 1., 3.0, 2.95])])]
    out = chip_smoke._compare_streams(tie, got, "t", tol=0.05)
    assert out["near_tie_flips"] == 1
    far = [([3, 5], [np.array([0., 1., 2., 9.]), np.array([0., 1., 2., 4.])])]
    with pytest.raises(SystemExit, match="logits differ"):
        chip_smoke._compare_streams(ref, far, "t", tol=0.05)


def test_no_chip_no_result(tmp_path):
    """Without an accelerator the script stops in its devices phase:
    non-zero, and no result line."""
    with pytest.raises(SystemExit, match="found no TPU"):
        chip_smoke.phase_devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and "found no TPU" in proc.stderr


def test_explicit_accelerator_context_raises_on_the_cpu():
    """mx.tpu(i)/mx.gpu(i) never mean "the host if there is nothing
    better"; asking for nothing may still get the CPU."""
    assert mx.num_tpus() == 0
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match="no accelerator"):
            ctx.jax_device
    assert mx.context.default_context() == mx.cpu(0)
    assert mx.cpu(0).jax_device.platform == "cpu"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives there and the
    package sets no other directory.  Unset: one fixed path inside the
    checkout.  Either way it is on from import."""
    code = (
        "import json, os, jax, mxnet_tpu as mx\n"
        "(mx.nd.ones((2, 3)) + 1).asnumpy()\n"
        "print(json.dumps({'mx': mx.aot.cache_dir(),"
        " 'jax': jax.config.jax_compilation_cache_dir,"
        " 'files': sorted(os.listdir(mx.aot.cache_dir()))}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if from_env:
        want = str(tmp_path / "placed")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.splitlines()[-1])
    assert got["mx"] == got["jax"] == want
    assert any(f.endswith("-cache") for f in got["files"])
    assert "mx_cache_index.json" in got["files"]
