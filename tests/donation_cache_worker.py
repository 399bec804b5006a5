"""Pure-jax reproduction of "a donated program served from the
persistent compilation cache corrupts its buffers" (no framework code).

    python tests/donation_cache_worker.py CACHE_DIR [donate|copy]

A donated SGD-momentum step, the persistent cache on at ``CACHE_DIR``
with every entry admitted, several chained steps (step n's donated
outputs are step n+1's inputs).  Run it twice with one directory: the
second process is served its executable from disk.  Prints one JSON
line with the cache hits it saw and a digest of the final state; the
``copy`` arm (no donation, no cache) is the reference the digests must
equal bit for bit.  tests/test_aot.py runs it on the CPU; PR 21 ran it
on a TPU v5e through the same two commands (CHANGES.md).
"""
import hashlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 6


def main(cache_dir, arm):
    hits = []
    if arm == "donate":
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        from jax import monitoring
        monitoring.register_event_listener(
            lambda e, **kw: hits.append(e)
            if e == "/jax/compilation_cache/cache_hits" else None)

    def step(w, mom, x, y):
        def loss(w):
            return jnp.mean((jnp.tanh(x @ w) - y) ** 2)
        g = jax.grad(loss)(w)
        mom = 0.9 * mom + g
        return w - 0.1 * mom, mom

    fn = jax.jit(step, donate_argnums=(0, 1) if arm == "donate" else ())
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(256, 256).astype(np.float32) * 0.05)
    mom = jnp.zeros_like(w)
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    y = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    for _ in range(STEPS):
        w, mom = fn(w, mom, x, y)       # chained: outputs feed the next
    w, mom = np.asarray(w), np.asarray(mom)
    print(json.dumps({
        "arm": arm, "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "jax": jax.__version__, "cache_hits": len(hits),
        "finite": bool(np.isfinite(w).all() and np.isfinite(mom).all()),
        "digest": hashlib.sha256(w.tobytes() + mom.tobytes()).hexdigest(),
    }))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else "donate")
