"""The channel-gated delta rule's Pallas pair (pallas/kda_delta_rule.py)
in interpret mode against the ``jax.numpy`` chunk routine
(ops/delta_rule.py ``chunk_kda_delta_rule``) at ONE geometry, 2 heads of
128 and 1024 tokens (two runs of 8 chunks a head, so the state crosses
a grid step in both directions): forward and all five gradients, with
gates that hold channels decaying by e^-20 inside a chunk beside
channels that do not decay; what ``supported`` takes; the launch labels.
The kernels' Mosaic lowering at the cell's size is
tests/test_chip_compile.py's."""
import jax
import jax.numpy as jnp

from test_kimi_linear import _scan_operands


def test_kernels_match_the_chunk_routine_forward_and_five_gradients():
    from mxnet_tpu.ops.delta_rule import gated_delta_rule
    from mxnet_tpu.pallas import kda_delta_rule as kda
    from mxnet_tpu.telemetry import REGISTRY
    args, do = _scan_operands(1024, 2, 128)
    assert kda.supported(*args[:3])[0]
    launches = REGISTRY.get("pallas_kernel_launches")
    kda._run_forward.clear_cache()
    kda._run_backward.clear_cache()
    before = {k: launches.labels(kernel=k).value
              for k in ("kda_delta_rule", "kda_delta_rule_bwd",
                        "gated_delta_rule")}
    both = [jax.jit(jax.value_and_grad(
        lambda *a, impl=impl: jnp.sum(gated_delta_rule(*a, impl=impl) * do),
        argnums=(0, 1, 2, 3, 4)))(*args) for impl in ("interpret", False)]
    assert float(jnp.abs(both[0][0] - both[1][0])) \
        <= 1e-5 * float(jnp.abs(both[1][0]))
    for name, got, want in zip("q k v g beta".split(), both[0][1],
                               both[1][1]):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert bool(jnp.isfinite(got).all()), name
        assert float(jnp.linalg.norm(got - want)) \
            <= 1e-5 * float(jnp.linalg.norm(want)), name
    # one build each, under labels of their own
    assert launches.labels(kernel="kda_delta_rule").value \
        == before["kda_delta_rule"] + 1
    assert launches.labels(kernel="kda_delta_rule_bwd").value \
        == before["kda_delta_rule_bwd"] + 1
    assert launches.labels(kernel="gated_delta_rule").value \
        == before["gated_delta_rule"]
    # the forward alone, a sequence that is no whole run: padded inside
    short = tuple(a[:, :, :600] for a in args)
    o = gated_delta_rule(*short, impl="interpret")
    want = gated_delta_rule(*short, impl=False)
    assert o.shape == want.shape
    assert float(jnp.abs(o - want).max()) <= 1e-5 * float(jnp.abs(want).max())


def test_supported_takes_one_value_head_a_key_head_of_128():
    from mxnet_tpu.pallas import kda_delta_rule as kda
    t = lambda *s, d=jnp.bfloat16: jax.ShapeDtypeStruct(s, d)
    wide = t(1, 32, 8192, 128)
    assert kda.supported(wide, wide, wide)[0]
    f32 = t(1, 2, 256, 128, d=jnp.float32)
    assert kda.supported(f32, f32, f32)[0]
    ok, why = kda.supported(t(1, 16, 8192, 128), t(1, 16, 8192, 128), wide)
    assert not ok and "heads=32/16" in why
    narrow = t(1, 32, 8192, 64)
    assert not kda.supported(narrow, narrow, narrow)[0]
    assert not kda.supported(wide, wide, t(1, 32, 8192, 256))[0]
    half = t(1, 32, 8192, 128, d=jnp.float16)
    assert not kda.supported(half, half, half)[0]
    try:
        kda.kda_delta_rule(*(jnp.zeros(s.shape, s.dtype) for s in (
            t(1, 2, 64, 64), t(1, 2, 64, 64), t(1, 2, 64, 64),
            t(1, 2, 64, 64, d=jnp.float32), t(1, 2, 64, d=jnp.float32))))
    except ValueError as e:
        assert "pallas kda delta rule" in str(e)
    else:
        raise AssertionError("a geometry the kernels refuse went through")


def test_the_mixers_scopes_and_where_its_kernels_run(monkeypatch):
    """``kda.proj``, ``kda.conv``, ``kda.gate``, ``kda.scan`` and
    ``kda.norm`` are in the compiled gradient; the convolution's kernels
    carry ``pallas.gdn_mix`` inside ``kda.conv`` and the scan's carry
    ``pallas.kda_delta_rule`` inside ``kda.scan`` (what
    ``kda_scan_roofline_share.train`` reads), forward and backward, and
    the forward scan kernel is built ONCE: the backward pass makes q, k,
    v, g and beta again and finds the run starts kept."""
    import re
    from mxnet_tpu.ops import delta_rule, nn
    H, D, d, S = 2, 128, 32, 128
    shapes = [(1, S, d), (H * D, d), (H * D, d), (H * D, d), (3 * H * D, 4),
              (D, d), (H * D, D), (H,), (H * D,), (H, d), (D, d), (H * D, D),
              (H * D,), (D,), (d, H * D)]
    args = [jnp.ones(s, jnp.float32) * 0.1 for s in shapes]
    monkeypatch.setattr(nn, "_gdn_mix_impl", lambda *a: "interpret")
    monkeypatch.setattr(delta_rule, "_delta_rule_impl",
                        lambda *a: "interpret")

    def loss(*a):
        return jnp.sum(nn.kimi_delta_attention(*a, heads=H, head_dim=D))

    text = jax.jit(jax.grad(loss, (0, 1, 6))).lower(*args).as_text(
        debug_info=True)
    unwrap = re.compile(r"^(?:(?:transpose|jvp)\()*([^()]*)\)*$")
    paths = [[unwrap.match(part).group(1) for part in name.split("/")
              if unwrap.match(part)]
             for name in re.findall(r'"jit\(loss\)/([^"]*)"', text)]
    seen = {part for path in paths for part in path}
    assert {"kda.proj", "kda.conv", "kda.gate", "kda.scan",
            "kda.norm"} <= seen
    kernels = {tuple(p for p in path if p.startswith(("kda.", "pallas.")))
               for path in paths if any(p.startswith("pallas.")
                                        for p in path)}
    assert kernels == {("kda.conv", "pallas.gdn_mix"),
                       ("kda.scan", "pallas.kda_delta_rule")}
    # made again in the backward pass: the convolution and the gates,
    # not the scan's forward kernel
    again = {p for path in paths if "rematted_computation" in path
             for p in path if p.startswith("kda.")}
    assert {"kda.conv", "kda.gate"} <= again
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 6)))(*args))
    assert jaxpr.count("name=kda_delta_rule_forward") == 1
    assert jaxpr.count("name=kda_delta_rule_backward") == 1
