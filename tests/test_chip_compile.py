"""The chip's compiler on the main path's kernels, without the chip.

libtpu is installed here, and it compiles for a TPU that is described
and not attached (``on-chip-measurement`` guide, section 2, third
rehearsal).  Interpret-mode parity (tests/test_pallas.py) says a kernel
computes the right thing; only Mosaic says whether it will START on the
chip — every paged kernel passed every interpret-mode test for eight PRs
while the chip's compiler refused all three (CHANGES.md, PR 21).  So the
kernels ``auto`` selects on a TPU are compiled here at the widths
chip_smoke.py runs them at: H16 D128, block 16, capacity 32, chunk 64,
d2048, S1024.  A compile that passes is not a chip run and says nothing
about results or times.

The topology is described inside a module-scoped fixture and nowhere
else: one process at a time may load libtpu (unless the environment
says ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, as the tier-1 command does),
xdist workers all import every test file, and only the worker that RUNS
this file may load it.  Compiles happen in the test's own process for
the same reason.

The families' whole-program compiles stay in THIS file, one after the
other on one worker (PR 43 tried a file a family and took it back).  A
whole program keeps 4.4 of the host's 8 cores busy for a minute (252 s
of CPU for 77 of wall): four at once, beside the other workers, took
204-293 s each.  And xdist hands files out by their number of tests,
largest first (``loadscopereorder``): a file of one to three tests is
handed out last, so files of their own put the suite's four longest
tests at the end of the run, all at once (the driver's three runs were
cut at 1 470 s with two of them still compiling).  A file of 33 tests
starts in the run's second minute.
"""
import functools
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

H, D, BS, C, M, K, NB = 16, 128, 16, 32, 64, 64, 256
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no libtpu, or it is held
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *specs):
    """Compile ``fn`` for the described chip over (shape, dtype) specs;
    the Mosaic kernel must be IN the program, not merely accepted."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    # jax's own matmul precision, as on the chip: conftest pins float32
    # for CPU parity tests, which is not what a deployment compiles
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def branch_kernels(text):
    """``{a conditional's op_name less the jit's: [the op_names of the
    Mosaic kernels each of its branches calls, whiles and fusions
    included]}`` of a compiled module's text."""
    bodies, name = {}, None
    for line in text.splitlines():
        start = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if start:
            name = start.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)

    def arms(line):
        found = re.search(r"branch_computations=\{([^}]*)\}", line)
        return [a.strip(" %") for a in found.group(1).split(",")] \
            if found else []

    def kernels(name):
        found = []
        for line in bodies[name]:
            if 'custom_call_target="tpu_custom_call"' in line:
                found.append(re.search(r'op_name="([^"]*)"', line).group(1))
            for other in re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                    line) + arms(line):
                found += kernels(other)
        return found

    out = {}
    for lines in bodies.values():
        for line in lines:
            if " conditional(" in line:
                op = re.search(r'op_name="([^"]*)"', line).group(1)
                out[op.split("/", 1)[1]] = [kernels(a) for a in arms(line)]
    return out


def on_the_chip(args, one_chip):
    """A prepared program's arguments as shapes on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)


def cell_config(name):
    """``benchmark/configs/<name>.json``."""
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def fit_program(cfg, one_chip, planes=None):
    """The fused fit program of a training cell at its own sizes (one
    sequence of ``seq_len`` tokens, the configuration's optimizer with
    float32 masters, ``ce`` folded), compiled for the described chip.
    ``planes``: the family's ``data`` is (1, planes, seq_len), not
    (1, seq_len) (a block-diffusion pass: ids, noised ids, weights).
    The kernel choices ask ``jax.default_backend()``, which is the CPU
    here: the caller steers them first, as the chip would answer.  The
    parameters stay the zeros they were bound as: the program is
    compiled over shapes, and an initializer would still draw every
    weight on the host (a family's variables carry their own ``Normal``,
    which wins over the one passed: 43-97 s a cell, for values nothing
    reads)."""
    import mxnet_tpu as mx
    kw = cfg["kwargs"]
    S = kw["seq_len"]
    mod = mx.Module(mx.models.get_symbol(cfg["model"], **kw),
                    context=mx.cpu())
    shape = (1, S) if planes is None else (1, planes, S)
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", (S,))])
    mod.init_params(initializer=None)
    mod.init_optimizer(optimizer=cfg["optimizer"], optimizer_params=dict(
        cfg["optimizer_params"], multi_precision=True))
    tokens = np.arange(S, dtype=np.float32) % kw["num_classes"]
    batch = mx.io.DataBatch(
        data=[mx.nd.array(np.broadcast_to(tokens, shape))],
        label=[mx.nd.array(tokens)])
    fn, args, _ = mod._get_fused_fit()._prepare(batch,
                                                mx.metric.create("ce"))
    with jax.default_matmul_precision("default"):
        return fn.lower(*on_the_chip(args, one_chip)).compile()


def device_bytes(compiled, label):
    """``memory_analysis``: arguments + outputs - aliased + temporaries,
    printed (a configuration's ``reduced_why`` quotes the line)."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print("%s fit program: arguments %.2f GB, outputs %.2f, aliased %.2f, "
          "temporaries %.2f: %.2f GB"
          % ((label,) + tuple(b / 1e9 for b in (
              m.argument_size_in_bytes, m.output_size_in_bytes,
              m.alias_size_in_bytes, m.temp_size_in_bytes, total))))
    return total


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_attend_compiles(one_chip, dtype):
    from mxnet_tpu.pallas import paged_decode_attend
    cache = ((NB, BS, H, D), dtype)
    _compile(functools.partial(paged_decode_attend, scale=SCALE), one_chip,
             ((C, H, D), dtype), cache, cache,
             ((C, M), jnp.int32), ((C,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_chunk_prefill_attend_compiles(one_chip, dtype):
    """The engine's one mixed step: a 64-token chunk of one prompt."""
    from mxnet_tpu.pallas import paged_chunk_prefill_attend
    chunk, cache = ((1, K, H, D), dtype), ((NB, BS, H, D), dtype)
    _compile(functools.partial(paged_chunk_prefill_attend, scale=SCALE),
             one_chip, chunk, chunk, chunk, cache, cache,
             ((1, M), jnp.int32), ((1,), jnp.int32), ((1,), jnp.int32))


def test_paged_chunk_prefill_attend_compiles_as_spec_verify(one_chip):
    """The same kernel as the speculative step calls it: one short
    span (spec_k 4 + 1 rows) per slot."""
    from mxnet_tpu.pallas import paged_chunk_prefill_attend
    dtype = jnp.bfloat16
    span, cache = ((C, 5, H, D), dtype), ((NB, BS, H, D), dtype)
    _compile(functools.partial(paged_chunk_prefill_attend, scale=SCALE),
             one_chip, span, span, span, cache, cache,
             ((C, M), jnp.int32), ((C,), jnp.int32), ((C,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_prefill_attend_compiles(one_chip, dtype):
    from mxnet_tpu.pallas import paged_prefill_attend
    rows, cache = ((4, 256, H, D), dtype), ((NB, BS, H, D), dtype)
    _compile(functools.partial(paged_prefill_attend, scale=SCALE),
             one_chip, rows, rows, rows, cache, cache,
             ((4, M), jnp.int32), ((4,), jnp.int32))


@pytest.mark.parametrize("with_residual", [False, True])
def test_layernorm_fused_fwd_bwd_compiles(one_chip, with_residual):
    from mxnet_tpu.pallas import layernorm_fused

    def loss(x, g, b, res):
        out, _, _ = layernorm_fused(x, g, b,
                                    residual=res if with_residual else None)
        return out.astype(jnp.float32).sum()

    x = ((4, 1024, 2048), jnp.bfloat16)
    vec = ((2048,), jnp.float32)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)
                                if with_residual else (0, 1, 2)),
             one_chip, x, vec, vec, x)


def test_two_bit_quantize_fused_compiles(one_chip):
    from mxnet_tpu.pallas import two_bit_quantize_fused
    g = ((2048, 8192), jnp.float32)
    _compile(lambda r, x: two_bit_quantize_fused(r, x, 0.5), one_chip, g, g)


def test_flash_attention_fwd_grad_compiles(one_chip):
    """The flash kernels (jax's splash attention forward, the repo's
    backward with a head's key/value rows resident) at the LM cell's
    geometry: 2 sequences of 2048, 16 heads of 128."""
    from mxnet_tpu.ops.nn import _flash_attention

    def loss(q, k, v):
        return _flash_attention(q, k, v).astype(jnp.float32).sum()

    qkv = ((2, H, 2048, D), jnp.bfloat16)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                    qkv, qkv, qkv).as_text()
    assert "flash_attention_backward" in text and "splash_mha_dkv" not in text


def test_fit_program_conditional_takes_gradients_narrow(one_chip):
    """A 2-layer bf16 transformer's fused fit program (Adam with f32
    masters, the loss scaler's ``cond``, the sentinel on), compiled for
    the described chip: among the conditional's operands the only
    float32 arrays of a bf16 parameter's shape are that parameter's
    optimizer state (mean, variance, master).  One more would be a
    float32 copy of its gradient: an operand of a conditional is a
    buffer in HBM, and the update widens the bf16 gradient itself."""
    import collections
    import re

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import fused_update, models

    S, V = 256, 384
    mod = mx.Module(models.get_symbol(
        "transformer", num_classes=V, num_layers=2, d_model=256,
        num_heads=2, ffn_dim=512, seq_len=S, dtype="bfloat16"),
        context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, S))],
             label_shapes=[("softmax_label", (2 * S,))])
    mod.init_params(mx.init.Normal(0.02))
    mod.init_optimizer(optimizer="adam", optimizer_params={
        "learning_rate": 2e-4, "wd": 0.1, "multi_precision": True})
    tokens = np.arange(2 * S, dtype=np.float32) % V
    batch = mx.io.DataBatch(data=[mx.nd.array(tokens.reshape(2, S))],
                            label=[mx.nd.array(tokens)])
    ff = mod._get_fused_fit()
    fn, args, _ = ff._prepare(batch, mx.metric.create("ce"))
    assert ff._scaler is not None
    params, states = args[0], args[1]
    assert {str(p.dtype) for p in params.values()} == {"bfloat16",
                                                       "float32"}
    with jax.default_matmul_precision("default"):
        text = fn.lower(*on_the_chip(args, one_chip)).compile().as_text()

    # the float32 arrays each parameter shape may have among the
    # operands: its state leaves, and for a float32 parameter (the
    # embeddings) itself and its gradient
    allowed = collections.Counter()
    for n, p in params.items():
        assert all(str(l.dtype) == "float32" for l in states[n])
        allowed[tuple(p.shape)] += len(states[n]) + (
            0 if fused_update.is_low_precision(p.dtype) else 2)

    types = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) [\w\-]+\(", text, re.M)}
    conds = re.findall(r"^.* conditional\((.*?)\), ", text, re.M)
    assert len(conds) == 1
    elements = set()
    for operand in re.findall(r"%([\w.\-]+)", conds[0])[1:]:
        line = re.search(r"^\s*%?" + re.escape(operand)
                         + r" = .*? tuple\((.*)\)", text, re.M)
        elements.update(re.findall(r"%([\w.\-]+)", line.group(1)))
    seen = collections.Counter()
    for e in elements:
        m = re.match(r"(\w+)\[([\d,]*)\]", types[e])
        if m and m.group(1) == "f32" and m.group(2):
            seen[tuple(int(d) for d in m.group(2).split(","))] += 1
    narrow = {tuple(p.shape) for p in params.values()
              if fused_update.is_low_precision(p.dtype)}
    assert narrow and all(seen[s] > 0 for s in narrow)
    assert {s: seen[s] for s in narrow} == {s: allowed[s] for s in narrow}


def test_grouped_expert_products_fwd_grad_compile(one_chip):
    """The dropless expert layer at the ZAYA cell's widths (8192 tokens,
    8 held of 16 experts, 2048 -> 2048 -> 2048 in bf16): sort, gather,
    the three grouped products over (held, out, in) stacks with the
    Pallas grouped matmul at the tiles ``parallel/moe.py`` picks,
    scatter, forward and backward.  The choice asks
    ``jax.default_backend()``, which is the CPU here: the test says
    ``impl`` as the chip would choose."""
    from mxnet_tpu.parallel.moe import dropless_top1_experts
    N, d, F, E, held = 8192, 2048, 2048, 16, 8

    def loss(x, logits, wg, wu, wd):
        y, counts = dropless_top1_experts(
            x, jax.nn.softmax(logits, -1), wg, wu, wd, held_first=0,
            impl="compiled")
        return y.astype(jnp.float32).sum() + counts.sum()

    stack = ((held, F, d), jnp.bfloat16)
    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
        ((N, d), jnp.bfloat16), ((N, E), jnp.float32), stack, stack,
        ((held, d, F), jnp.bfloat16))
    assert "ragged" not in compiled.as_text()


@pytest.mark.parametrize("cell, N, k, held, E, d, F", [
    ("smallthinker", 16384, 6, 16, 64, 2560, 768),
    ("keye", 16384, 8, 16, 128, 2048, 768),
    ("qwen3next", 8192, 10, 32, 512, 2048, 512)])
def test_topk_expert_layer_with_the_token_ordered_sum_compiles(
        one_chip, cell, N, k, held, E, d, F):
    """The dropless top-k layer at three cells' shapes (Kanana-2's is
    Qwen3-Next's with other experts), forward and backward with the
    kernels the chip would choose: beside the grouped products the
    token-ordered sum, twice (the combine, weighted: three bfloat16
    passes; the gather's gradient), in both arms of the sized buffer,
    and no wide ``scatter`` is left in the program."""
    from mxnet_tpu.parallel.moe import _row_buckets, dropless_topk_experts
    assert len(_row_buckets(N, k, held, E)) == 2

    def loss(x, experts, weights, wg, wu, wd):
        y, counts = dropless_topk_experts(
            x, experts, weights, wg, wu, wd, E, 0, impl="compiled")
        return y.astype(jnp.float32).sum() + counts.sum()

    stack = ((held, F, d), jnp.bfloat16)
    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 2, 3, 4, 5)), one_chip,
        ((N, d), jnp.bfloat16), ((N, k), jnp.int32), ((N, k), jnp.float32),
        stack, stack, ((held, d, F), jnp.bfloat16)).as_text()
    assert "token_sum" in text and "gmm" in text
    wide = [line for line in text.splitlines()
            if " scatter(" in line and "%d]" % d in line.split("scatter(")[0]]
    assert not wide, wide[:2]
    # the small arm goes back from what its forward kept: three grouped
    # products forward, and their six other directions backward, none of
    # the forward's a second time (nine until PR 46); the worst arm still
    # runs a slab's three again before its six
    products = {
        which: [sum("pallas.grouped_matmul" in name for name in arm)
                for arm in arms]
        for which, arms in branch_kernels(text).items()}
    assert products == {"jvp()/cond": [3, 3],
                        "transpose(jvp())/cond": [6, 9]}, products


def test_gated_delta_rule_fwd_grad_compiles(one_chip):
    """The chunked gated delta rule's kernels at the Qwen3-Next cell's
    sizes (one sequence of 8192 tokens, 16 key heads serving 32 value
    heads of 128, bf16): the forward alone, and the gradient's forward
    (which keeps the float32 state each run of 8 chunks starts from:
    34 MB a layer, the only residual besides the inputs) and backward
    kernel."""
    from mxnet_tpu.ops.delta_rule import gated_delta_rule
    B, Hk, Hv, S, D = 1, 16, 32, 8192, 128
    shapes = (((B, Hk, S, D), jnp.bfloat16), ((B, Hk, S, D), jnp.bfloat16),
              ((B, Hv, S, D), jnp.bfloat16), ((B, Hv, S), jnp.float32),
              ((B, Hv, S), jnp.float32))
    rule = lambda *a: gated_delta_rule(*a, impl="compiled")
    text = _compile(rule, one_chip, *shapes).as_text()
    assert "tpu_custom_call" in text and "gated_delta_rule_forward" in text
    grad = _compile(
        jax.grad(lambda *a: rule(*a).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2, 3, 4)), one_chip, *shapes)
    text = grad.as_text()
    assert "tpu_custom_call" in text
    assert "gated_delta_rule_forward" in text
    assert "gated_delta_rule_backward" in text
    assert grad.memory_analysis().temp_size_in_bytes < 50e6


def test_kda_delta_rule_fwd_grad_compiles(one_chip):
    """The channel-gated delta rule's kernels at the Kimi-Linear cell's
    sizes (one sequence of 8192 tokens, 32 heads each with its own keys,
    128 wide, bf16; the gate (S, 128) a head, float32): the forward
    alone, and the gradient's forward (which keeps the float32 state
    each run of chunks starts from) and backward kernel."""
    from mxnet_tpu.ops.delta_rule import gated_delta_rule
    from mxnet_tpu.pallas import kda_delta_rule as kda
    B, H, S, D = 1, 32, 8192, 128
    wide = ((B, H, S, D), jnp.bfloat16)
    shapes = (wide, wide, wide, ((B, H, S, D), jnp.float32),
              ((B, H, S), jnp.float32))
    rule = lambda *a: gated_delta_rule(*a, impl="compiled")
    text = _compile(rule, one_chip, *shapes).as_text()
    assert "tpu_custom_call" in text and "kda_delta_rule_forward" in text
    grad = _compile(
        jax.grad(lambda *a: rule(*a).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2, 3, 4)), one_chip, *shapes)
    text = grad.as_text()
    assert "kda_delta_rule_forward" in text
    assert "kda_delta_rule_backward" in text
    # the run starts are the only residual besides the inputs
    starts = B * H * (S // (kda._RUN * 64)) * D * D * 4
    assert grad.memory_analysis().temp_size_in_bytes < starts + 50e6


def test_gdn_mix_fwd_grad_compiles(one_chip):
    """Gated DeltaNet's convolution, SiLU and L2 norms as kernels
    (``pallas/gdn_mix.py``) at the Qwen3-Next cell's sizes (one sequence
    of 8192 tokens, 16 + 16 + 32 heads of 128, 4 taps, bf16): the forward
    alone, and forward + backward, whose temporaries are no larger than
    the ``jax.numpy`` form's (which writes the float32 activations of all
    64 heads: 537 MB forward, 1 074 MB with the backward pass)."""
    from mxnet_tpu.ops.nn import gdn_mix
    B, Hk, Hv, S, D, K = 1, 16, 32, 8192, 128, 4
    shapes = (((B, 2 * Hk + Hv, S, D), jnp.bfloat16),
              (((2 * Hk + Hv) * D, K), jnp.bfloat16))

    def both(impl):
        def loss(qkv, w):
            return sum(t.astype(jnp.float32).sum() ** 2
                       for t in gdn_mix(qkv, w, Hk, impl=impl))
        return jax.value_and_grad(loss, argnums=(0, 1))

    fwd = _compile(lambda a, b: gdn_mix(a, b, Hk, impl="compiled"),
                   one_chip, *shapes)
    assert "gdn_mix_forward" in fwd.as_text()
    assert fwd.memory_analysis().temp_size_in_bytes < 1e6
    grad = _compile(both("compiled"), one_chip, *shapes)
    text = grad.as_text()
    assert "gdn_mix_forward" in text and "gdn_mix_backward" in text
    with jax.default_matmul_precision("default"):
        plain = jax.jit(both(False)).lower(*(
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes)).compile()
    assert "tpu_custom_call" not in plain.as_text()
    kernels = grad.memory_analysis().temp_size_in_bytes
    assert kernels < 300e6 < plain.memory_analysis().temp_size_in_bytes


def test_compressed_conv_attention_fwd_grad_compiles_with_flash(
        one_chip, monkeypatch):
    """CCA at the ZAYA cell's widths (one sequence of 8192, 8 query to 2
    key/value heads of 128, d 2048, bf16) with the flash kernel, which
    takes K and V at their own two heads and shares each among its four
    query heads.  The kernel choice asks ``jax.default_backend()``,
    which is the CPU here: the test steers it, as the chip would
    answer."""
    from mxnet_tpu.ops import nn
    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    S, d, Hq, Hk, Dh = 8192, 2048, 8, 2, 128

    def loss(h, *ws):
        return nn.compressed_conv_attention(
            h, *ws, q_heads=Hq, kv_heads=Hk, head_dim=Dh) \
            .astype(jnp.float32).sum()

    bf = jnp.bfloat16
    compiled = _compile(
        jax.value_and_grad(loss, argnums=tuple(range(8))), one_chip,
        ((1, S, d), bf), ((Hq * Dh, d), bf), ((Hk * Dh, d), bf),
        ((2 * Dh, d), bf), ((10 * Dh, 2), bf), ((10, Dh, Dh, 2), bf),
        ((Hk,), bf), ((d, Hq * Dh), bf))
    assert "splash_mha" in compiled.as_text()


def _written_types(text):
    """The result type of every instruction of a compiled program that
    is written to memory: the instructions of the entry computation and
    of the branches it calls, not those inside a fusion (a fusion's
    interior lives in registers and VMEM)."""
    import re
    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                         text):
        if "fused_computation" in comp.split("(", 1)[0]:
            continue
        for m in re.finditer(
                r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+?)\{[^ ]*\} (?!parameter|tuple)"
                r"[\w\-]+\(", comp, re.M):
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("cell,B,S,V,bias", [
    ("cgpt13b", 2, 2048, 50257, True), ("zaya1_8b", 1, 8192, 32784, False)])
def test_fit_program_writes_no_float32_probabilities(one_chip, cell, B, S,
                                                     V, bias):
    """The tail of a training cell at its own widths (bf16 hidden states
    (B, S, 2048) -> ``lm_head`` -> ``Cast`` float32 -> ``Reshape`` ->
    ``SoftmaxOutput``; Adam with float32 masters), as the fused fit
    program, compiled for the described chip.  With ``ce`` folded the
    program returns the head's stem and writes NO float32 array of
    tokens x vocabulary elements: neither a result, nor the operand of
    the metric's read at the labels.  The program of a metric that
    accumulates on the host returns the probabilities, as every fit
    program did before (three writes of 823 MB a step on the LM cell:
    PERF.md section 6, PR 29), and shows them here."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import sym

    d = 2048

    def program(metric):
        x = sym.Cast(sym.Variable("data"), dtype="bfloat16", name="cast_in")
        x = sym.FullyConnected(data=x, num_hidden=V, flatten=False,
                               no_bias=not bias, name="lm_head")
        x = sym.Cast(data=x, dtype="float32", name="cast_out")
        x = sym.Reshape(data=x, shape=(-1, V), name="logits_2d")
        mod = mx.Module(sym.SoftmaxOutput(data=x, name="softmax",
                                          normalization="batch"),
                        context=mx.cpu())
        mod.bind(data_shapes=[("data", (B, S, d))],
                 label_shapes=[("softmax_label", (B * S,))])
        mod.init_params(mx.init.Zero())
        mod.init_optimizer(optimizer="adam", optimizer_params={
            "learning_rate": 2e-4, "wd": 0.1, "multi_precision": True})
        batch = mx.io.DataBatch(data=[mx.nd.zeros((B, S, d))],
                                label=[mx.nd.zeros((B * S,))])
        fn, args, _ = mod._get_fused_fit()._prepare(batch, metric)
        assert str(args[0]["lm_head_weight"].dtype) == "bfloat16"
        with jax.default_matmul_precision("default"):
            return fn.lower(*on_the_chip(args, one_chip)).compile().as_text()

    wide = {"f32[%d,%d]" % (B * S, V), "f32[%d,%d,%d]" % (B, S, V)}
    stem = "bf16[%d,%d,%d]" % (B, S, V)

    deferred = _written_types(program(mx.metric.create("ce")))
    assert stem in deferred
    assert not wide & set(deferred)

    returned = _written_types(program(mx.metric.np(lambda l, p: 0.0)))
    assert wide & set(returned)


def test_qwen3_next_fit_program_compiles_and_fits_the_chip(one_chip,
                                                           monkeypatch):
    """The fused fit program of the cell ``qwen3next_80b_train_ep16`` at
    its own sizes (4 layers, 32 of 512 experts held, 18 992 rows of the
    vocabulary, one sequence of 8192 tokens, bf16 with f32 masters),
    compiled for the described chip with the kernels the chip would
    choose: the flash kernel at head_dim 256 with 8 query heads to a
    key/value head, the Pallas grouped matmul in every size of the
    sorted rows' buffer, the gated delta rule's two kernels (forward,
    backward) and the two of the convolution, SiLU and L2 norms before
    it.  ``memory_analysis`` (arguments + outputs -
    aliased + temporaries) stays under 15 GB of the chip's 16: the
    configuration's ``reduced_why`` quotes the number printed here.  The
    kernel choices ask ``jax.default_backend()``, which is the CPU here:
    the test steers them, as the chip would answer."""
    from mxnet_tpu.ops import delta_rule, nn
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(delta_rule, "_delta_rule_impl",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(nn, "_gdn_mix_impl", lambda *a, **k: "compiled")
    cfg = cell_config("qwen3_next_80b_train")
    kw = cfg["kwargs"]
    assert (kw["num_layers"], kw["experts_held"], kw["seq_len"]) \
        == (4, [0, 32], 8192)
    compiled = fit_program(cfg, one_chip)
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "gmm" in text and "ragged" not in text
    assert "flash_attention_backward" in text and "splash_mha_dkv" not in text
    for kernel in ("forward", "backward"):
        assert "gated_delta_rule_" + kernel in text
        assert "gdn_mix_" + kernel in text
    assert device_bytes(compiled, "qwen3_next") < 15e9


def test_flash_attention_at_192_and_128_fwd_grad_compiles(one_chip):
    """The flash kernel at latent attention's geometry (the Kanana-2
    cell): one sequence of 8192, 32 heads, queries and keys 192 wide,
    values 128; the backward holds a head's 8192 rows of k, v and of the
    float32 dk, dv in VMEM (38.8 MB: where an overrun would show)."""
    from mxnet_tpu.ops.nn import _flash_attention

    def loss(q, k, v):
        return _flash_attention(q, k, v).astype(jnp.float32).sum()

    qk = ((1, 32, 8192, 192), jnp.bfloat16)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                    qk, qk, ((1, 32, 8192, 128), jnp.bfloat16)).as_text()
    assert "flash_attention_backward" in text and "splash_mha_dkv" not in text


def test_kanana2_fit_program_compiles_and_fits_the_chip(one_chip,
                                                        monkeypatch):
    """The fused fit program of the cell ``kanana2_30b_train_ep8`` at
    its own sizes (the dense layer and 4 expert layers, 16 of 128
    experts held, 16 032 rows of the vocabulary, one sequence of 8192
    tokens, bf16 with f32 masters), compiled for the described chip
    with the kernels the chip would choose: the flash kernel at key
    width 192 / value width 128 and the Pallas grouped matmul over 16
    groups of width 768 in both sizes of the sorted rows' buffer.
    ``memory_analysis`` (arguments + outputs - aliased + temporaries)
    stays under 15 GB of the chip's 16: the configuration's
    ``reduced_why`` quotes the number printed here.  The kernel choices
    ask ``jax.default_backend()``, which is the CPU here: the test
    steers them, as the chip would answer."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    cfg = cell_config("kanana2_30b_train")
    kw = cfg["kwargs"]
    assert (kw["num_layers"], kw["experts_held"], kw["seq_len"]) \
        == (5, [0, 16], 8192)
    compiled = fit_program(cfg, one_chip)
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "gmm" in text and "ragged" not in text
    assert "flash_attention_backward" in text and "splash_mha_dkv" not in text
    assert device_bytes(compiled, "kanana2") < 15e9


def test_kimi_linear_fit_program_compiles_and_fits_the_chip(one_chip,
                                                            monkeypatch):
    """The fused fit program of the cell ``kimilinear_48b_train_ep32``
    at its own sizes (a KDA layer with the dense FFN, then KDA, KDA,
    latent attention without position and KDA with 8 of 256 experts
    held, 20 480 rows of the vocabulary, one sequence of 8192 tokens,
    bf16 with f32 masters), compiled for the described chip with the
    kernels the chip would choose: the channel-gated delta rule's pair,
    the convolution's pair before it, the flash kernel at key width 192
    / value width 128 and the Pallas grouped matmul over 8 groups of
    width 1024 in both sizes of the sorted rows' buffer.
    ``memory_analysis`` stays under 15 GB of the chip's 16: the
    configuration's ``reduced_why`` quotes the number printed here."""
    from mxnet_tpu.ops import delta_rule, nn
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(delta_rule, "_delta_rule_impl",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(nn, "_gdn_mix_impl", lambda *a, **k: "compiled")
    cfg = cell_config("kimi_linear_48b_train")
    kw = cfg["kwargs"]
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"],
            kw["seq_len"]) == (5, [0, 8], 20480, 8192)
    compiled = fit_program(cfg, one_chip)
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "gmm" in text and "ragged" not in text
    assert "flash_attention_backward" in text
    for kernel in ("forward", "backward"):
        assert "kda_delta_rule_" + kernel in text
        assert "gdn_mix_" + kernel in text
        assert "gated_delta_rule_" + kernel not in text
    assert device_bytes(compiled, "kimi_linear") < 15e9


def test_keye_vl2_fit_program_compiles_and_fits_the_chip(one_chip,
                                                         monkeypatch):
    """The fused fit program of the cell ``keyevl2_30b_train_ep8`` at
    its own sizes (4 layers, 16 of 128 experts held, 18 992 rows of the
    vocabulary, one sequence of 16 384 tokens, bf16 with f32 masters and
    a float32 index scorer), compiled for the described chip with the
    kernels the chip would choose: the Pallas grouped matmul for the
    expert layer, and for the sparse indexed attention the pairs of
    ``pallas/sparse_attention.py`` (the heads' cores) and of
    ``pallas/index_scorer.py`` (the scorer's S x S work) and the kernel
    of ``pallas/topk_choice.py`` (the choice between them): all five
    custom calls are in the compiled text, and nothing fell back.
    ``memory_analysis`` (arguments + outputs - aliased + temporaries)
    stays under 15 GB of the chip's 16; PR 38's program, whose cores
    were XLA loops, read 13.54 GB (the configuration's ``reduced_why``
    quotes that one), PR 41's 13.62.  At a scorer width the kernels do
    not take the one decision is the XLA loops, counted, not an
    error."""
    from mxnet_tpu.ops import sparse_attention
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    # `auto` as on the chip: a one-device TPU program
    monkeypatch.setattr(dispatch, "_compiles_here", lambda: (True, "", None))
    fallbacks = lambda: sum(c.value
                            for c in dispatch.PALLAS_FALLBACKS.children())
    before = fallbacks()
    cfg = cell_config("keye_vl2_30b_train")
    kw = cfg["kwargs"]
    S = kw["seq_len"]
    assert (kw["num_layers"], kw["experts_held"], kw["topk"]) \
        == (4, [0, 16], 2048)
    assert S in (16384, 8192)
    compiled = fit_program(cfg, one_chip)
    text = compiled.as_text()
    assert "gmm" in text and "ragged" not in text
    assert "token_sum" in text
    assert "dsa.select" in text and "dsa.index_loss" in text
    assert "sparse_attention_forward" in text
    assert "sparse_attention_backward" in text
    assert "index_scorer_forward" in text
    assert "index_scorer_backward" in text
    assert "topk_choice" in text.replace("pallas.topk_choice", "")
    assert fallbacks() == before
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    wide = jax.ShapeDtypeStruct((1, 16, S, 128), jnp.float32)
    assert sparse_attention._cores_impl(
        shape(1, 32, S, 128), shape(1, 4, S, 128), wide, 512, 512, S) is False
    assert fallbacks() == before + 1
    assert device_bytes(compiled, "keye_vl2") < 15e9


@pytest.mark.parametrize("S", [16384, 8192])
def test_topk_choice_compiles(one_chip, S):
    """The choice's kernel alone at the Keye cell's sizes (a block of
    512 rows on a row of 16 384 float32 scores, ``topk`` 2048) and at
    half the length: Mosaic takes the dynamic lane slices, the int8
    mask, its packed bits and the tie rule's bfloat16 product, and the
    call holds no memory of its own beside its operands."""
    from mxnet_tpu.pallas import topk_choice
    assert topk_choice.supported(jnp.float32, 512, 512, S)[0]
    compiled = _compile(
        lambda ib, r0, n: topk_choice.choose(ib, r0, n, 2048, 512, 2048),
        one_chip, ((512, S), jnp.float32), ((), jnp.int32), ((), jnp.int32))
    assert "topk_choice" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_banded_flash_pair_fwd_grad_compiles(one_chip):
    """The flash pair with a band at the SmallThinker cell's geometry:
    one sequence of 16 384, 28 query heads to 4 key/value heads of 128,
    window 4096.  Both kernels are in the program (the splash forward
    under a ``LocalMask``, the repo's backward walking the band), and
    the backward holds a key/value head's whole 16 384 rows of k, v and
    of the float32 dk, dv in VMEM (50.3 MB in one segment: where an
    overrun would show).  The causal pair at the same geometry beside
    it: the model's full layers run it at a length no cell had."""
    from mxnet_tpu.ops.nn import _flash_attention
    from mxnet_tpu.pallas import flash_backward as fb
    q = ((1, 28, 16384, 128), jnp.bfloat16)
    kv = ((1, 4, 16384, 128), jnp.bfloat16)
    z = fb.plan(16384, 128, 128, jnp.bfloat16)
    assert (z.segments, z.rows) == (1, 16384)
    for window in (4096, None):
        def loss(q, k, v):
            return _flash_attention(q, k, v, window=window) \
                .astype(jnp.float32).sum()

        text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        one_chip, q, kv, kv).as_text()
        assert "splash_mha_fwd" in text
        assert "flash_attention_backward" in text
        assert "splash_mha_dkv" not in text


def test_smallthinker_fit_program_compiles_and_fits_the_chip(one_chip,
                                                             monkeypatch):
    """The fused fit program of the cell ``smallthinker_21b_train_s16k``
    at its own sizes (4 layers: one full without position, three of
    window 4096 with rotary; 16 of 64 experts held, 18 992 rows of the
    vocabulary, one sequence of 16 384 tokens, bf16 with f32 masters),
    compiled for the described chip with the kernels the chip would
    choose: the flash pair, banded in three layers, and the Pallas
    grouped matmul (nothing fell back).  ``memory_analysis`` (arguments
    + outputs - aliased + temporaries) stays under 15 GB of the chip's
    16: the configuration's ``reduced_why`` quotes the number printed
    here."""
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    # `auto` as on the chip: a one-device TPU program
    monkeypatch.setattr(dispatch, "_compiles_here", lambda: (True, "", None))
    fallbacks = lambda: sum(c.value
                            for c in dispatch.PALLAS_FALLBACKS.children())
    launches = lambda name: dispatch.PALLAS_LAUNCHES.labels(kernel=name).value
    before = fallbacks()
    banded, causal = (launches("flash_attention_window"),
                      launches("flash_attention"))
    cfg = cell_config("smallthinker_21b_train")
    kw = cfg["kwargs"]
    assert (kw["num_layers"], kw["experts_held"], kw["window"],
            kw["seq_len"]) == (4, [0, 16], 4096, 16384)
    compiled = fit_program(cfg, one_chip)
    text = compiled.as_text()
    assert "gmm" in text and "ragged" not in text
    assert "token_sum" in text
    assert "splash_mha_fwd" in text and "flash_attention_backward" in text
    for scope in ("gqa.proj", "gqa.rope", "gqa.window", "gqa.full"):
        assert scope in text, scope
    assert fallbacks() == before
    # three banded layers to one full, however often the step is traced
    banded, causal = (launches("flash_attention_window") - banded,
                      launches("flash_attention") - causal)
    assert causal >= 1 and banded == 3 * causal
    assert device_bytes(compiled, "smallthinker") < 15e9


def test_block_diffusion_flash_pair_fwd_grad_compiles(one_chip):
    """The flash pair under the block-diffusion mask at the SDAR cell's
    geometry: a clean and a noised half of 8192 rows (16 384 in all), 32
    query heads to 4 key/value heads of 128, block length 4.  Both
    kernels are in the program: the splash forward over the lazy mask
    (its cells computed in the kernel from the row numbers), the repo's
    backward walking the table's four steps (a run and three masked
    blocks with shifts and compares Mosaic has to take), a key/value
    head's 16 384 rows resident in one segment."""
    from mxnet_tpu.ops.nn import _flash_attention
    from mxnet_tpu.pallas import flash_backward as fb
    q = ((1, 32, 16384, 128), jnp.bfloat16)
    kv = ((1, 4, 16384, 128), jnp.bfloat16)
    z = fb.plan(16384, 128, 128, jnp.bfloat16)
    assert (z.segments, z.rows) == (1, 16384)

    def loss(q, k, v):
        return _flash_attention(q, k, v, blocks=4).astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    one_chip, q, kv, kv).as_text()
    assert "splash_mha_fwd" in text
    assert "flash_attention_backward" in text
    assert "splash_mha_dkv" not in text


def test_sdar_fit_program_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """The fused fit program of the cell ``sdar_30b_train_bd4_s8k`` at
    its own sizes (4 layers, 16 of 128 experts held, 18 992 rows of the
    vocabulary, one sequence of 8192 tokens as 16 384 rows, block length
    4, bf16 with f32 masters), compiled for the described chip with the
    kernels the chip would choose: the flash pair under the
    block-diffusion mask in every layer and the Pallas grouped matmul
    (nothing fell back).  ``memory_analysis`` (arguments + outputs -
    aliased + temporaries) stays under 15 GB of the chip's 16: the
    configuration's ``reduced_why`` quotes the number printed here.  The
    head's stem is the bfloat16 rows of the noised half: no float32
    (8192, 18 992) array is written."""
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(moe, "_grouped_matmul_impl",
                        lambda *a, **k: "compiled")
    # `auto` as on the chip: a one-device TPU program
    monkeypatch.setattr(dispatch, "_compiles_here", lambda: (True, "", None))
    fallbacks = lambda: sum(c.value
                            for c in dispatch.PALLAS_FALLBACKS.children())
    launches = lambda name: dispatch.PALLAS_LAUNCHES.labels(kernel=name).value
    before = fallbacks()
    masked, causal = (launches("flash_attention_blocks"),
                      launches("flash_attention"))
    cfg = cell_config("sdar_30b_a3b_train")
    kw = cfg["kwargs"]
    assert (kw["num_layers"], kw["experts_held"], kw["block_length"],
            kw["seq_len"]) == (4, [0, 16], 4, 8192)
    compiled = fit_program(cfg, one_chip, planes=3)
    text = compiled.as_text()
    assert "gmm" in text and "ragged" not in text
    assert "token_sum" in text
    assert "splash_mha_fwd" in text and "flash_attention_backward" in text
    for scope in ("gqa.proj", "gqa.norm", "gqa.blockdiff",
                  "head.diffusion"):
        assert scope in text, scope
    assert "gqa.full" not in text and "gqa.rope" not in text
    assert fallbacks() == before
    assert launches("flash_attention_blocks") > masked
    assert launches("flash_attention") == causal
    assert "f32[8192,18992]" not in _written_types(text)
    assert device_bytes(compiled, "sdar_moe") < 15e9


@pytest.mark.parametrize("cell,S_,D,Dv,rows,buckets", [
    ("cgpt13b_train_s2048", 2048, 128, None, None, None),
    ("zaya1_8b_train_ep2", 8192, 128, None, (8192, 1, 8, 16), [8192]),
    ("qwen3next_80b_train_ep16", 8192, 256, None, (8192, 10, 32, 512),
     [8192, 81920]),
    ("kanana2_30b_train_ep8", 8192, 192, 128, (8192, 6, 16, 128),
     [8192, 49152]),
    ("keyevl2_30b_train_ep8", 16384, None, None, (16384, 8, 16, 128),
     [20480, 131072]),
    ("smallthinker_21b_train_s16k", 16384, 128, None, (16384, 6, 16, 64),
     [30720, 98304]),
    # rows that choose alike: twice the even share (``rows_slack``)
    ("sdar_30b_train_bd4_s8k", 16384, 128, None, (16384, 8, 16, 128, 2.0),
     [32768, 131072]),
    # 256 rows an expert: the smaller size is one row a token
    ("kimilinear_48b_train_ep32", 8192, 192, 128, (8192, 8, 8, 256),
     [8192, 65536]),
])
def test_accepted_cells_geometries_give_what_they_gave(cell, S_, D, Dv, rows,
                                                       buckets):
    """What PR 38 must not have moved for the four accepted
    language-model cells: the flash forward's tiles and the backward's
    plan from their sequence and head widths, and the sorted rows'
    sizes of their expert layers (the smaller size is five quarters of
    the expected count only where that passes one row a token: in the
    Keye cell, whose expected count IS one row a token).  No topology
    is described here."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.parallel import moe
    from mxnet_tpu.pallas import flash_backward as fb
    if D is not None:
        bs = nn._flash_block_sizes(S_)
        assert (bs.block_q, bs.block_kv, bs.block_kv_compute) \
            == (1024, 1024, 512)
        z = fb.plan(S_, D, Dv or D, jnp.bfloat16)
        assert (z.segments, z.rows, z.transposed) == (1, S_, D == 192)
    if rows is not None:
        assert moe._row_buckets(*rows) == buckets
        # top-1 and an expected count under a row a token: as before PR 38
        tokens, k, held, E = rows[:4]
        if tokens * k * held < tokens * E:
            assert buckets[0] == tokens
