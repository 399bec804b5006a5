"""Percent of the sparse indexed attention's score tiles (``q_chunk`` x
``kv_chunk``, on or under the diagonal, all layers) that held at least
one chosen (query, key) pair in the last step of the window: the
program's gauge ``dsa_live_block_share``, filled from the step's count
output when read (``mxnet_tpu.telemetry.dsa.publish``).  100 says a
kernel that skips dead tiles would skip none.  None for a program
without the counter."""


def read(facts):
    try:
        from mxnet_tpu.telemetry import dsa
    except ImportError:
        return None
    if dsa.publish() is None:
        return None
    return 100.0 * dsa.LIVE_BLOCK_SHARE.value
