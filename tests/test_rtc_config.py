"""mx.rtc (Pallas kernels) + MXNET_* env config tests."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_pallas_module_kernel():
    def axpy(a_ref, x_ref, y_ref, o_ref):
        o_ref[...] = a_ref[...] * x_ref[...] + y_ref[...]

    mod = mx.rtc.PallasModule(axpy=axpy)
    k = mod.get_kernel("axpy", out_shape=(8,), out_dtype="float32",
                       interpret=True)
    a = nd.array(np.full((8,), 2.0, np.float32))
    x = nd.array(np.arange(8, dtype=np.float32))
    y = nd.array(np.ones((8,), np.float32))
    out = k.launch([a, x, y], mx.cpu())
    np.testing.assert_allclose(out.asnumpy(), 2 * np.arange(8) + 1)
    # callable sugar + repeat launches reuse the compiled callable
    np.testing.assert_allclose(k(a, x, y).asnumpy(), out.asnumpy())


def test_pallas_module_grid():
    from jax.experimental import pallas as pl

    def scale(x_ref, o_ref):
        i = pl.program_id(0)
        o_ref[i, :] = x_ref[i, :] * 3.0

    mod = mx.rtc.PallasModule(scale=scale)
    k = mod.get_kernel("scale", out_shape=(4, 8), out_dtype="float32",
                       grid=(4,), interpret=True)
    x = nd.array(np.ones((4, 8), np.float32))
    np.testing.assert_allclose(k.launch([x]).asnumpy(), 3.0)


def test_cuda_module_raises_with_guidance():
    with pytest.raises(mx.MXNetError, match="Pallas"):
        mx.rtc.CudaModule("__global__ void f(){}")


def test_unknown_kernel_name():
    mod = mx.rtc.PallasModule(f=lambda x_ref, o_ref: None)
    with pytest.raises(mx.MXNetError):
        mod.get_kernel("g", out_shape=(1,))


def test_config_summary_lists_known_vars():
    s = mx.config.summary()
    assert "MXNET_ENGINE_TYPE" in s
    assert "inert" in s and "yes" in s


def _run_snippet(code, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240,
                          cwd=ROOT)


def test_naive_engine_blocks_eagerly():
    code = (
        "import mxnet_tpu as mx, numpy as np\n"
        "from mxnet_tpu import config\n"
        "assert config.naive_engine()\n"
        "x = mx.nd.array(np.ones((4,)))\n"
        "y = x + x\n"
        "print('naive ok', float(y.asnumpy()[0]))\n")
    proc = _run_snippet(code, {"MXNET_ENGINE_TYPE": "NaiveEngine"})
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "naive ok 2.0" in proc.stdout


def test_backward_do_mirror_trains():
    """Remat path produces the same training result as the default."""
    code = (
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import sym\n"
        "mx.random.seed(0); np.random.seed(0)\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.rand(32, 6).astype('float32')\n"
        "y = (X.sum(1) > 3).astype('float32')\n"
        "net = sym.SoftmaxOutput(sym.FullyConnected(sym.Variable('data'),"
        " num_hidden=2, name='fc'), name='softmax')\n"
        "it = mx.io.NDArrayIter(X, y, batch_size=16)\n"
        "mod = mx.Module(net, context=mx.cpu())\n"
        "mod.fit(it, num_epoch=3, optimizer='sgd',\n"
        "        initializer=mx.initializer.Uniform(0.1))\n"
        "print('W', float(mod.get_params()[0]['fc_weight'].asnumpy()"
        ".sum()))\n")
    base = _run_snippet(code, {})
    mirrored = _run_snippet(code, {"MXNET_BACKWARD_DO_MIRROR": "1"})
    assert base.returncode == 0, base.stderr[-1500:]
    assert mirrored.returncode == 0, mirrored.stderr[-1500:]
    w0 = float(base.stdout.split("W ")[1])
    w1 = float(mirrored.stdout.split("W ")[1])
    assert abs(w0 - w1) < 1e-4  # same math, different memory schedule


def test_backward_do_mirror_is_a_fwd_bwd_cache_key():
    """Two binds of the SAME symbol under flipped MXNET_BACKWARD_DO_MIRROR
    must select DIFFERENT cached fwd_bwd programs (the flag is part of
    the per-symbol cache key, and each executor snapshots it at bind
    time) with matching gradients — before the mx.analyze retrace pass
    flagged this (PR 9), the second bind silently reused the first
    bind's program, so the knob appeared to work but did nothing."""
    from mxnet_tpu import sym
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=4,
                           name="mirfc"), name="softmax")
    xb = np.random.RandomState(3).rand(8, 6).astype(np.float32)
    yb = np.zeros((8,), np.float32)

    def bind_and_grad():
        exe = net.simple_bind(ctx=mx.cpu(), grad_req="write",
                              data=(8, 6), softmax_label=(8,))
        return exe

    prev = os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
    try:
        e_plain = bind_and_grad()
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
        e_mirror = bind_and_grad()
    finally:
        if prev is None:
            os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
        else:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = prev
    assert e_plain._mirror is False and e_mirror._mirror is True
    assert e_plain._jit_fwd_bwd is not e_mirror._jit_fwd_bwd, \
        "mirror flip must select a different cached fwd_bwd program"
    # the env flip after e_plain's bind must not retroactively change it
    assert e_plain._mirror is False

    def grads(exe):
        for n, src in e_plain.arg_dict.items():
            exe.arg_dict[n]._set_data(src._data)
        exe.forward(is_train=True, data=xb, softmax_label=yb)
        exe.backward()
        return exe.grad_dict["mirfc_weight"].asnumpy().copy()

    # remat reorders FMA contraction: rtol-level equality, not bitwise
    np.testing.assert_allclose(grads(e_plain), grads(e_mirror),
                               rtol=2e-6, atol=1e-8)
