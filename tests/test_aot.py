"""mx.aot: persistent compiled-program cache + AOT warmup manifests.

Covers the zero-cold-start contract (docs/AOT.md): manifest capture ->
warm round-trips in the same process AND across a real process restart
(subprocess arms share JAX_COMPILATION_CACHE_DIR); a persistent-cache hit
serves the bit-identical program while booking ``aot_cache_hits``; a
corrupted index or cache entry falls back to a fresh compile instead of
failing the deploy; ModelServer construction warms every bucket through
the thread pool compiling each exactly once; the program registry's
(site, signature) guard keeps AOT and live-traffic registrations in ONE
entry with the ``warmed`` flag.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot, serving, telemetry
from mxnet_tpu.executor import EXECUTOR_RETRACES
from mxnet_tpu.serving.replica import manifest_buckets

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# shared by in-process fixtures and the subprocess restart arms: the
# model must be IDENTICAL across processes or the jit signatures (and
# persistent-cache keys) won't line up
MODEL_SRC = r'''
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import aot, serving, telemetry
from mxnet_tpu.executor import EXECUTOR_RETRACES

def build():
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=16, name="fc1"),
        act_type="relu")
    sym = mx.sym.softmax(
        mx.sym.FullyConnected(h, num_hidden=8, name="fc2"),
        name="softmax")
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(1, 12))
    params = {n: rng.normal(0, 0.05, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), shapes)
              if n != "data"}
    return sym, params

def serve(**kw):
    sym, params = build()
    return serving.ModelServer(sym, params, {}, {"data": (12,)},
                               max_batch_size=4, **kw)
'''

_ns = {}
exec(MODEL_SRC, _ns)
_serve = _ns["serve"]


def _run_py(code, env_extra=None, timeout=300):
    """Run a fresh interpreter on MODEL_SRC + code; returns the last
    JSON line.  Every arm gets the IDENTICAL jax config (cache keys
    cover compile options, so a config fork turns hits into misses)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MXNET_AOT_MANIFEST", None)
    env.update(env_extra or {})
    if "JAX_COMPILATION_CACHE_DIR" not in env:
        # the package's cache is on by default (<checkout>/.jax_cache);
        # an arm that is given no directory runs with jax's cache off
        env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    proc = subprocess.run([sys.executable, "-c", MODEL_SRC + code],
                          env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.programs.clear()
    yield
    telemetry.programs.clear()


# ----------------------------------------------------------------------
# manifests: capture -> warm, same process
# ----------------------------------------------------------------------
def test_manifest_roundtrip_same_process(tmp_path):
    srv = _serve(warmup=True)
    try:
        srv.predict({"data": np.zeros(12, np.float32)})
        m = aot.capture(site="executor")
        assert len(m["entries"]) == len(srv._buckets)
        for e in m["entries"]:
            assert e["site"] == "executor" and e["treedef"]
            assert all(s is None or (s[0] and isinstance(s[1], list))
                       for s in e["arg_specs"])
        path = aot.save(m, str(tmp_path / "model.aot.json"))
        m2 = aot.load(path)
        assert m2["entries"] == m["entries"]
        ok, reason = aot.compatible(m2)
        assert ok, reason
        # the manifest names exactly the server's bucket ladder
        base = srv._pool.replicas[0]._base
        assert manifest_buckets(m2["entries"], base.input_shapes,
                                srv._buckets) == srv._buckets
    finally:
        srv.stop()
    # a fresh server warmed from the manifest serves its first request
    # with zero retraces (the shared per-symbol trace cache in-process;
    # the cross-process form is test_manifest_subprocess_restart)
    srv2 = _serve(warmup_manifest=m)
    try:
        before = EXECUTOR_RETRACES.value
        srv2.predict({"data": np.zeros(12, np.float32)})
        assert EXECUTOR_RETRACES.value - before == 0
    finally:
        srv2.stop()


def test_manifest_load_rejects_garbage(tmp_path):
    bad = tmp_path / "not-a-manifest.json"
    bad.write_text("{broken")
    with pytest.raises(mx.MXNetError, match="cannot read manifest"):
        aot.load(str(bad))
    bad.write_text(json.dumps({"no": "entries"}))
    with pytest.raises(mx.MXNetError, match="not an AOT manifest"):
        aot.load(str(bad))


def test_incompatible_manifest_falls_back(monkeypatch):
    """Version/backend drift must NEVER fail a deploy: the server warms
    its full ladder cold, mx.aot.warm reports the skip reason."""
    srv = _serve(warmup=True)
    try:
        m = aot.capture(site="executor")
    finally:
        srv.stop()
    stale = dict(m, jax="0.0.0-stale")
    out = aot.warm(stale)
    assert out["warmed"] == 0 and "0.0.0-stale" in out["skipped"]
    before = EXECUTOR_RETRACES.value
    srv2 = _serve(warmup_manifest=stale)    # logs + full cold warmup
    try:
        # the fallback warmed the FULL ladder (fresh symbol => fresh
        # trace cache): one compile per bucket, none left for traffic
        delta = EXECUTOR_RETRACES.value - before
        assert delta == len(srv2._buckets)
        b0 = EXECUTOR_RETRACES.value
        srv2.predict({"data": np.zeros(12, np.float32)})
        assert EXECUTOR_RETRACES.value - b0 == 0
    finally:
        srv2.stop()


def test_default_path_knob(monkeypatch):
    monkeypatch.delenv("MXNET_AOT_MANIFEST", raising=False)
    assert aot.default_path() is None
    monkeypatch.setenv("MXNET_AOT_MANIFEST", "/tmp/m.json")
    assert aot.default_path() == "/tmp/m.json"


# ----------------------------------------------------------------------
# satellite 2: construction-time warmup, threaded, exactly once
# ----------------------------------------------------------------------
def test_server_warmup_compiles_each_bucket_exactly_once(monkeypatch):
    monkeypatch.setenv("MXNET_AOT_WARMUP_THREADS", "4")
    before = EXECUTOR_RETRACES.value
    srv = _serve(warmup=True)
    try:
        delta = EXECUTOR_RETRACES.value - before
        assert delta == len(srv._buckets), (delta, srv._buckets)
        # and the registry agrees: one program per bucket, no
        # double-registration from the concurrent warmup
        progs = telemetry.programs(analyze=False, site="executor")
        assert len(progs) == len(srv._buckets)
        # traffic over warmed buckets never retraces
        b0 = EXECUTOR_RETRACES.value
        for _ in range(3):
            srv.predict({"data": np.zeros(12, np.float32)})
        assert EXECUTOR_RETRACES.value - b0 == 0
    finally:
        srv.stop()


def test_scale_up_replica_warms_before_start():
    srv = _serve(warmup=True)
    try:
        idx = srv.add_replica(ctx=mx.cpu(1))
        assert idx == 1
        assert sorted(srv._pool.replicas[1]._preds) == srv._buckets
        srv.predict({"data": np.zeros(12, np.float32)})
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# program registry: dedup guard + warmed flag
# ----------------------------------------------------------------------
def test_programs_dedup_and_warmed_flag():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.telemetry import programs as P

    f = jax.jit(lambda x: x + 1)
    args = (jnp.ones((4, 4)),)
    f(*args)
    compiled = f.lower(*args).compile()
    # same (site, signature) registered twice -> ONE entry
    P.register_compiled("executor", compiled, fn_name="<lambda>",
                        signature=args)
    P.register_compiled("executor", compiled, fn_name="<lambda>",
                        signature=args)
    rows = telemetry.programs(analyze=False, site="executor")
    assert len(rows) == 1 and rows[0]["warmed"] is False
    # an AOT re-registration under warming() upgrades the flag in place
    with P.warming():
        P.register_compiled("executor", compiled, fn_name="<lambda>",
                            signature=args)
    rows = telemetry.programs(analyze=False, site="executor")
    assert len(rows) == 1 and rows[0]["warmed"] is True
    # live-traffic record() of the same signature merges too
    P.record("executor", f, args, compile_ms=1.0)
    rows = telemetry.programs(analyze=False, site="executor")
    assert len(rows) == 1
    sigs = P.export_signatures(site="executor")
    assert len(sigs) == 1 and sigs[0]["warmed"] is True
    assert sigs[0]["arg_specs"] == [["float32", [4, 4]]]


# ----------------------------------------------------------------------
# persistent cache: corrupt index heals, never fatal
# ----------------------------------------------------------------------
def test_corrupt_index_heals(tmp_path, monkeypatch):
    cache = tmp_path / "cache"
    d = aot.enable_persistent_cache(str(cache))
    try:
        assert d == str(cache) and aot.cache_dir() == d
        idx_path = cache / "mx_cache_index.json"
        assert idx_path.exists()
        errs0 = aot.stats()["index_errors"]
        idx_path.write_text("{definitely not json")
        idx = aot.store.load_index()
        assert idx["programs"] == {}                 # healed, empty
        assert aot.stats()["index_errors"] == errs0 + 1
        # version mismatch is discarded the same way
        idx_path.write_text(json.dumps(
            {"format": -1, "jax": "x", "programs": {}}))
        assert aot.store.load_index()["programs"] == {}
        assert aot.stats()["index_errors"] == errs0 + 2
        # re-enable over the corrupt file rewrites a valid index
        aot.enable_persistent_cache(str(cache))
        assert json.loads(idx_path.read_text())["format"] == \
            aot.store.FORMAT_VERSION
    finally:
        aot.disable_persistent_cache()


# ----------------------------------------------------------------------
# cross-process: restart warm + cache hit + corrupt-entry fallback
# ----------------------------------------------------------------------
_SEED = r'''
import json
srv = serve(warmup=True)
srv.predict({"data": __import__("numpy").zeros(12, "float32")})
aot.save(aot.capture(site="executor"), %(manifest)r)
srv.stop()
print(json.dumps({"misses": aot.stats()["cache_misses"]}))
'''

_RESTART = r'''
import json
import numpy as np
srv = serve(warmup_manifest=%(manifest)r)
warmed = [p for p in telemetry.programs(analyze=False, site="executor")
          if p["warmed"]]
r0 = EXECUTOR_RETRACES.value
out = srv.predict({"data": np.ones(12, np.float32)})
first_retraces = EXECUTOR_RETRACES.value - r0
srv.stop()
st = aot.stats()
print(json.dumps({
    "warmed_programs": len(warmed),
    "first_request_retraces": first_retraces,
    "cache_hits": st["cache_hits"],
    "output": np.asarray(out[0]).tolist(),
}))
'''


def test_manifest_subprocess_restart(tmp_path):
    """The deploy recipe end to end: a seed process captures the
    manifest and populates the persistent cache; a REAL fresh process
    warms from both and serves its first request with zero retraces,
    bit-identically to a cache-less restart (same program, loaded from
    disk), with its programs flagged warmed."""
    manifest = str(tmp_path / "model.aot.json")
    cache = str(tmp_path / "cache")
    seed = _run_py(_SEED % {"manifest": manifest},
                   {"JAX_COMPILATION_CACHE_DIR": cache})
    assert seed["misses"] > 0                # seed populated the cache
    # restart WITHOUT the cache: warmup compiles, first request doesn't
    warm = _run_py(_RESTART % {"manifest": manifest})
    assert warm["warmed_programs"] == 3      # one per bucket [1, 2, 4]
    assert warm["first_request_retraces"] == 0
    assert warm["cache_hits"] == 0
    # restart WITH the cache: same contract plus disk-loads
    cached = _run_py(_RESTART % {"manifest": manifest},
                     {"JAX_COMPILATION_CACHE_DIR": cache})
    assert cached["warmed_programs"] == 3
    assert cached["first_request_retraces"] == 0
    assert cached["cache_hits"] > 0
    # the persistent-cache hit served the bit-identical program
    assert cached["output"] == warm["output"]


def test_corrupt_cache_entry_falls_back(tmp_path):
    """Flipping bytes in every cached executable must not break a
    restart: jax rejects the corrupt entries and the process falls back
    to fresh compiles — same outputs, zero first-request retraces."""
    manifest = str(tmp_path / "model.aot.json")
    cache = str(tmp_path / "cache")
    _run_py(_SEED % {"manifest": manifest},
            {"JAX_COMPILATION_CACHE_DIR": cache})
    corrupted = 0
    for dirpath, _, files in os.walk(cache):
        for name in files:
            if name == "mx_cache_index.json":
                continue
            path = os.path.join(dirpath, name)
            with open(path, "r+b") as f:
                f.write(b"\x00" * 64)
            corrupted += 1
    assert corrupted > 0
    out = _run_py(_RESTART % {"manifest": manifest},
                  {"JAX_COMPILATION_CACHE_DIR": cache})
    assert out["first_request_retraces"] == 0
    reference = _run_py(_RESTART % {"manifest": manifest})
    assert out["output"] == reference["output"]


# ----------------------------------------------------------------------
# donation under the cache: a donated program served from disk computes
# what the freshly compiled one does (some jax releases did not; the
# pure-jax reproduction tells on the installed one)
# ----------------------------------------------------------------------
def test_donated_program_from_cache_is_bit_equal(tmp_path):
    def arm(which):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "tests", "donation_cache_worker.py"),
             str(tmp_path / "cache"), which],
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.splitlines()[-1])

    truth, seeded, restarted = arm("copy"), arm("donate"), arm("donate")
    assert seeded["cache_hits"] == 0
    assert restarted["cache_hits"] > 0       # its step came from disk
    assert truth["finite"] and restarted["finite"]
    assert truth["digest"] == seeded["digest"] == restarted["digest"]


def test_cache_on_keeps_donation(tmp_path):
    """With the persistent cache on, program builders still donate."""
    aot.enable_persistent_cache(str(tmp_path / "cache"))
    try:
        sym, params = _ns["build"]()
        exe = sym.simple_bind(mx.cpu(), data=(1, 12))
        for n, v in params.items():
            exe.arg_dict[n][:] = v
        assert exe.donate_args(["fc1_weight"]) is True
        assert exe._jit_fwd_eval_donated is not None
    finally:
        aot.disable_persistent_cache()


_FIT = r'''
import hashlib, json
import numpy as np
import mxnet_tpu as mx

rng = np.random.RandomState(11)
X = rng.rand(64, 12).astype("float32")
y = (X.sum(axis=1) > 6).astype("float32")
sym, params = build()
train = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False)
mod = mx.Module(mx.sym.SoftmaxOutput(sym.get_children()[0],
                                     name="softmax"),
                context=mx.cpu())
mod.bind(data_shapes=train.provide_data, label_shapes=train.provide_label)
mod.set_params({n: mx.nd.array(v) for n, v in params.items()}, {})
mod.fit(train, num_epoch=3, optimizer="adam",
        optimizer_params={"learning_rate": 0.01}, eval_metric="acc")
args, _ = mod.get_params()
h = hashlib.sha256()
for n in sorted(args):
    h.update(args[n].asnumpy().tobytes())
st = aot.stats()
print(json.dumps({"hash": h.hexdigest(),
                  "hits": st["cache_hits"], "misses": st["cache_misses"]}))
'''


def test_fit_restart_cache_bitidentical(tmp_path):
    """Training correctness across a cached restart: a fused-fit run
    whose DONATED programs disk-load must produce the EXACT weights of
    a cache-less run."""
    cache = str(tmp_path / "cache")
    truth = _run_py(_FIT)
    seeded = _run_py(_FIT, {"JAX_COMPILATION_CACHE_DIR": cache})
    restarted = _run_py(_FIT, {"JAX_COMPILATION_CACHE_DIR": cache})
    assert seeded["misses"] > 0              # first cached run populates
    assert restarted["misses"] == 0          # restart is all disk-loads
    assert restarted["hits"] > 0
    assert truth["hash"] == seeded["hash"] == restarted["hash"]
