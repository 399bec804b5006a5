"""An engine iteration's share of the bandwidth roofline, in percent:
the bytes one iteration must read (the configuration's reference
module gives ``serve_iter_bytes``: every layer's and the head's weights
once, plus the K/V rows of the live context of the sequences in it) over the chip's HBM bandwidth, over the
device-busy time per iteration from the trace.  Bound by bandwidth: a
decode iteration does two operations for each weight byte it reads.

Live rows come from the engine's own block accounting
(mean_cache_occupancy x blocks x block size), less half a block for
each occupied slot: a sequence's last block is half full on average."""
import common


def read(facts):
    tr = facts.get("trace")
    if facts.get("kind") != "serve" or not tr:
        return None
    a, b = facts["engine_before"], facts["engine_after"]
    steps = b["steps"] - a["steps"]
    if not steps:
        return None
    ek, kw = facts["config"]["engine"], facts["config"]["kwargs"]
    slots = (b["slot_occ_sum"] - a["slot_occ_sum"]) / steps
    blocks = (b["cache_occ_sum"] - a["cache_occ_sum"]) / steps \
        * ek["num_blocks"]
    rows = max(blocks * ek["block_size"] - slots * ek["block_size"] / 2.0,
               0.0)
    chunk = (b["prefill_chunks"] - a["prefill_chunks"]) / steps \
        * ek["chunk_tokens"]
    model = common.reference_model(facts["config"])
    if not hasattr(model, "serve_iter_bytes"):
        raise SystemExit("step_roofline_share.serve: reference/%s.py gives "
                         "no serve_iter_bytes(kwargs, rows, tokens)"
                         % facts["config"]["reference"])
    need = model.serve_iter_bytes(kw, rows, slots + chunk)
    least_s = need / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (tr["busy_s"] / steps)
