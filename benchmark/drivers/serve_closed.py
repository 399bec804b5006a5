"""Driver ``serve_closed``: a closed loop of ``clients`` threads against
``ModelServer`` ``POST /generate`` (streamed) in front of a
``DecodeEngine`` that holds the configuration's weights, made on the
device from the seed.

Each client posts its next request when its last finished.  The
requests come from one deck of (prompt length, new tokens) pairs fixed
by the traffic file; the seed only shuffles the deck and draws the
token ids, so every seed gives the same work in another order.  The
loop runs before the window until every client has finished one
request (set-up), then ``seconds`` are measured, then the clients stop
asking and what is in flight finishes.

``correct``: once the window has closed, a sample of the requests it
finished (drawn from the seed, the longest in it) runs once through the
plain float32 reference, prompt and served tokens together; each served
token's logit may lie below the reference's best by at most the limit.
"""
import http.client
import json
import math
import threading
import time

import numpy as np

import common
from reference import train as ref_train


# ----------------------------------------------------------------------
# traffic: one general generator over the file's parameters
# ----------------------------------------------------------------------
def quantiles(spec, n):
    """``n`` whole-number sizes at evenly spaced quantiles of ``spec``
    (``{"dist": "loguniform"|"uniform"|"fixed", "lo", "hi"}``)."""
    lo, hi = float(spec["lo"]), float(spec.get("hi", spec["lo"]))
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo)
    elif spec["dist"] == "fixed":
        v = np.full(n, lo)
    else:
        raise ValueError("unknown dist %r" % spec["dist"])
    return np.rint(v).astype(int)


def make_deck(traffic, vocab, seed):
    """[(prompt tokens, max_new_tokens)]: the same multiset of sizes for
    every seed (prompt and output quantiles paired by a fixed
    permutation).  The first ``clients`` requests, the ones the warm-up
    loop waits for, have the same sizes in the same order for every
    seed, so set-up is the same work; the seed shuffles the rest and
    draws every token id."""
    n, first = int(traffic["deck"]), int(traffic["clients"])
    plens = quantiles(traffic["prompt_len"], n)
    news = quantiles(traffic["max_new_tokens"], n)
    fixed = np.random.default_rng(int(traffic.get("pairing", 1)))
    news = news[fixed.permutation(n)]
    head = fixed.permutation(n)
    rng = np.random.default_rng([int(seed), 0xC4A7])
    order = np.concatenate([head[:first], rng.permutation(head[first:])])
    return [(rng.integers(0, vocab, int(plens[i])).tolist(), int(news[i]))
            for i in order]


# ----------------------------------------------------------------------
# clients
# ----------------------------------------------------------------------
class Record:
    __slots__ = ("client", "prompt", "max_new", "t_send", "t_tokens",
                 "tokens", "ok", "error", "t_done")

    def __init__(self, client, prompt, max_new):
        self.client, self.prompt, self.max_new = client, prompt, max_new
        self.t_send = self.t_done = None
        self.t_tokens, self.tokens = [], []
        self.ok, self.error = False, None


def post_stream(host, port, rec):
    """One streamed POST /generate; fills ``rec`` with each token and
    the time it reached the client."""
    body = json.dumps({"tokens": rec.prompt, "max_new_tokens": rec.max_new,
                       "stream": True})
    conn = http.client.HTTPConnection(host, port, timeout=300)
    try:
        rec.t_send = time.perf_counter()
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = "status %d: %s" % (resp.status, resp.read()[:200])
            return
        for raw in resp:
            now = time.perf_counter()
            if not raw.strip():
                continue
            doc = json.loads(raw)
            if doc.get("done"):
                if "error" in doc:
                    rec.error = str(doc["error"])
                else:
                    rec.ok = len(rec.tokens) > 0
                resp.read()             # the chunked body's terminator
                break
            rec.tokens.append(int(doc["token"]))
            rec.t_tokens.append(now)
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec.error = "%s: %s" % (type(e).__name__, e)
    finally:
        rec.t_done = time.perf_counter()
        conn.close()


class Loop:
    """The closed loop: ``clients`` threads drawing from one deck."""

    def __init__(self, host, port, deck, clients):
        self.host, self.port, self.deck = host, port, deck
        self.lock = threading.Lock()
        self.next = 0
        self.records = []
        self.stop = threading.Event()
        self.first_done = [threading.Event() for _ in range(clients)]
        self.threads = [threading.Thread(target=self._client, args=(c,),
                                         name="bench-client-%d" % c)
                        for c in range(clients)]

    def _client(self, c):
        while not self.stop.is_set():
            with self.lock:
                prompt, max_new = self.deck[self.next % len(self.deck)]
                self.next += 1
                rec = Record(c, prompt, max_new)
                self.records.append(rec)
            post_stream(self.host, self.port, rec)
            self.first_done[c].set()
            if rec.error is not None:
                time.sleep(0.05)        # never spin on a failing server

    def start(self):
        for t in self.threads:
            t.start()

    def wait_warm(self, timeout):
        for ev in self.first_done:
            if not ev.wait(timeout):
                raise SystemExit("serve_closed: a client's first request "
                                 "did not finish in %d s" % timeout)

    def finish(self, timeout=300):
        self.stop.set()
        for t in self.threads:
            t.join(timeout)
        if any(t.is_alive() for t in self.threads):
            raise SystemExit("serve_closed: a client thread did not end")


def engine_counters(eng):
    """The engine's own counts (``DecodeEngine.stats()``), with the
    running sums its means are made of."""
    st = eng.stats()
    steps = st["steps"] or 0
    return {
        "steps": steps,
        "dispatches": st["decode_step_dispatches"],
        "slot_occ_sum": (st["mean_slot_occupancy"] or 0.0) * steps,
        "cache_occ_sum": (st["mean_cache_occupancy"] or 0.0) * steps,
        "prefill_chunks": st["prefill_chunks"],
        "tokens": st["tokens_generated"],
        "completed": st["completed"], "failed": st["failed"],
        "preemptions": st["preemptions"],
        "retraces": st["steady_state_retraces"],
        "attn_impl": st["attn_impl"],
    }


# ----------------------------------------------------------------------
# correctness
# ----------------------------------------------------------------------
def sample_finished(records, n, seed):
    """``n`` finished requests: the longest (prompt + served), the rest
    drawn from the seed."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    done.sort(key=lambda r: (r.t_send, r.client))
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3B])
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in take]


def served_gaps(model, key, kw, rows, pick_len, precision="f32"):
    """(f32 logits at the served positions, mask of real positions)."""
    logits = model.served_token_gaps(key, kw, rows, pick_len, precision)
    mask = np.zeros((len(rows), pick_len), bool)
    for i, (_, s) in enumerate(rows):
        mask[i, :min(len(s), pick_len)] = True
    return logits, mask


def gap_matrix(ref_logits, tokens, mask):
    """Per position, how far the token's reference logit lies below the
    reference's best (0 at padding)."""
    import jax.numpy as jnp
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              axis=-1)[..., 0]
    return np.where(mask, np.asarray(best - got), 0.0)


def widest_gap(ref_logits, tokens, mask):
    """The widest such gap over the real positions."""
    return float(np.max(gap_matrix(ref_logits, tokens, mask)))


def token_matrix(rows, pick_len):
    out = np.zeros((len(rows), pick_len), np.int32)
    for i, (_, s) in enumerate(rows):
        out[i, :min(len(s), pick_len)] = s[:pick_len]
    return out


def check_served(model, key, cfg, traffic, rows):
    """The check row for served tokens (``rows`` as sample_finished's
    records turned to (prompt, tokens))."""
    pick_len = int(traffic["max_new_tokens"]["hi"])
    ref, mask = served_gaps(model, key, cfg["kwargs"], rows, pick_len)
    gaps = gap_matrix(ref, token_matrix(rows, pick_len), mask)
    detail = [{"prompt": len(p), "served": len(t), "widest": float(g.max()),
               "at": int(g.argmax()), "not_best": int((g > 0).sum()),
               "first_over_0.1": int(np.argmax(g > 0.1)) if (g > 0.1).any()
               else -1,
               "mean": float(g.sum() / max(len(t), 1))}
              for (p, t), g in zip(rows, gaps)]
    print("served-token gaps by request: %s" % json.dumps(detail))
    return common.check("served_logit_gap_widest[%d tokens]" % int(mask.sum()),
                        float(gaps.max()),
                        cfg["limits"]["served_logit_gap"]), ref, mask


def control_gap(model, key, cfg, traffic, rows, ref, mask):
    """The control's reading on the same prompts and tokens: the gap, in
    the float32 reference, of the token fp8 arithmetic puts first."""
    import jax.numpy as jnp
    pick_len = int(traffic["max_new_tokens"]["hi"])
    low, _ = served_gaps(model, key, cfg["kwargs"], rows, pick_len, "fp8")
    first = np.asarray(jnp.argmax(low, axis=-1))
    return common.check("control_fp8_logit_gap_widest",
                        widest_gap(ref, first, mask),
                        cfg["limits"]["served_logit_gap"])


# ----------------------------------------------------------------------
def tiny_forward(mx):
    """ModelServer wants a forward model beside the decode engine."""
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    return sym, {"fc_weight": mx.nd.zeros((2, 4)),
                 "fc_bias": mx.nd.zeros((2,))}


def build(cell, model, key):
    """(engine, server, host, port) with the seeded weights."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cfg = cell.config
    kw, ek = dict(cfg["kwargs"]), cfg["engine"]
    ctx = mx.cpu(0) if cell.rehearse else mx.tpu(0)
    specs = model.param_specs(kw)
    pd = cfg["param_dtypes"]
    dtypes = {n: jnp.dtype(pd.get(n, pd["default"])) for n, _ in specs}
    # made on the device from the seed; constants (ones, zeros) land on
    # jax's default device, so everything is put where the engine is
    weights = jax.device_put(
        ref_train.init_params(model, key, specs, dtypes), ctx.jax_device)
    params = {n: mx.nd.NDArray(w, ctx) for n, w in weights.items()}
    del weights
    eng = mx.decode.DecodeEngine(
        params, kw, capacity=int(ek["capacity"]),
        block_size=int(ek["block_size"]), num_blocks=int(ek["num_blocks"]),
        chunk_tokens=int(ek["chunk_tokens"]), ctx=ctx, warmup=True,
        max_waiting=max(256, 4 * int(cell.traffic["clients"])))
    del params
    sym, fwd = tiny_forward(mx)
    server = mx.serving.ModelServer(sym, fwd, {}, {"data": (4,)},
                                    contexts=[ctx], max_batch_size=1,
                                    warmup=False, decode_engine=eng)
    host, port = server.start_http(port=0)
    return eng, server, host, port


def run(cell, extra_checks=None):
    import jax
    model = common.reference_model(cell.config)
    cfg, traffic = cell.config, cell.traffic
    kw = dict(cfg["kwargs"])
    compiles = common.CompileCounter()
    key = model.seed_key(cell.seed)
    deck = make_deck(traffic, int(kw["num_classes"]), cell.seed)
    eng, server, host, port = build(cell, model, key)
    loop = Loop(host, port, deck, int(traffic["clients"]))
    try:
        loop.start()
        loop.wait_warm(600)
        # ---- the window ----------------------------------------------
        seconds = cell.seconds
        before_prog = common.program_counters()
        compiles0 = compiles.n
        e0 = engine_counters(eng)
        t0 = time.perf_counter()
        setup_s = t0 - cell.t_process
        trace_facts = None
        if cell.trace:
            seconds = min(seconds, float(traffic.get("trace_seconds", 3.0)))
            with common.traced(cell, True) as tr:
                time.sleep(seconds)
            trace_facts = common.reduce_trace(tr["dir"], ["window"],
                                              cell.rehearse)
        else:
            time.sleep(seconds)
        t1 = time.perf_counter()
        e1 = engine_counters(eng)
        compiled = compiles.n - compiles0
        after_prog = common.program_counters()
        loop.finish()
    finally:
        loop.stop.set()
        server.stop()
        e_end = engine_counters(eng)
        eng.stop()

    recs = list(loop.records)
    started = [r for r in recs if t0 <= r.t_send < t1]
    ttfts = [(r.t_tokens[0] - r.t_send) * 1e3 if r.ok else float("inf")
             for r in started]
    gaps, tokens_in = [], 0
    for r in recs:
        ts = r.t_tokens
        tokens_in += sum(1 for t in ts if t0 <= t < t1)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    failed = sum(1 for r in recs if r.error is not None)
    finished = [r for r in recs if r.ok and r.t_done is not None
                and t0 <= r.t_done]
    window_s = t1 - t0

    # ---- correct: served tokens against the plain reference ----------
    t_ref = time.perf_counter()
    sample = sample_finished(finished, int(traffic["check_requests"]),
                             cell.seed)
    rows = [(r.prompt, r.tokens) for r in sample]
    checks = []
    if rows:
        row, ref, mask = check_served(model, key, cfg, traffic, rows)
        checks.append(row)
        if extra_checks:
            checks += extra_checks(model, key, cfg, traffic, rows, ref, mask)
    else:
        checks.append(common.check("finished_requests_to_compare", 0, 0,
                                   ok=False))
    ref_seconds = time.perf_counter() - t_ref
    checks.append(common.check("compilations_in_window", compiled, 0))
    checks.append(common.check("steady_state_retraces", e_end["retraces"], 0))
    checks.append(common.check("failed_requests", failed, 0))
    checks.append(common.check("engine_failed", e_end["failed"], 0))

    facts = {
        "kind": "serve", "config": cfg, "traffic": traffic,
        "window_s": window_s, "engine_before": e0, "engine_after": e1,
        "before": before_prog, "after": after_prog, "trace": trace_facts,
        "rehearse": cell.rehearse,
    }
    if not cell.rehearse:
        import counts
        facts["peaks"] = counts.peaks(jax.devices()[0].device_kind)
    return {
        "end_to_end": {
            "serve_tok_per_s": tokens_in / window_s,
            "ttft_p90_ms": common.percentile(ttfts, 0.90) or float("inf"),
            "gap_p95_ms": common.percentile(gaps, 0.95) or float("inf"),
            "setup_s": setup_s,
        },
        "checks": checks, "attempted": len(recs), "failed": failed,
        "facts": facts,
        "notes": {"requests_started_in_window": len(started),
                  "requests_finished_in_window": sum(
                      1 for r in finished if r.t_done < t1),
                  "gaps": len(gaps), "tokens_in_window": tokens_in,
                  "ttft_p50_ms": common.percentile(ttfts, 0.5),
                  "gap_p50_ms": common.percentile(gaps, 0.5),
                  "iterations": e1["steps"] - e0["steps"],
                  "iter_ms": 1e3 * window_s / max(e1["steps"] - e0["steps"],
                                                  1),
                  "preemptions": e_end["preemptions"],
                  "attn_impl": e_end["attn_impl"],
                  "checked_requests": len(rows),
                  "reference_seconds": ref_seconds},
    }


def control(cell):
    """A short window at the cell's own load, then the program's reading
    and the fp8 control's on the same prompts and served tokens."""
    rows = []

    def both(model, key, cfg, traffic, r, ref, mask):
        return [control_gap(model, key, cfg, traffic, r, ref, mask)]

    cell.seconds = float(cell.traffic.get("control_seconds", 5.0))
    res = run(cell, extra_checks=both)
    for c in res["checks"]:
        if c["name"].startswith(("served_logit", "control_fp8")):
            rows.append(c)
    return rows
