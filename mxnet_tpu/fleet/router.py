"""Cache-aware request routing across decode replicas (docs/FLEET.md).

A paged-cache replica is not stateless: the prefix trie it has already
published makes SOME prompts nearly free (shared blocks skip prefill)
and others expensive.  Routing by least-loaded alone throws that state
away — two requests sharing a long system prompt land on different
replicas and each pays full prefill.  :class:`FleetRouter` routes by
PREFIX AFFINITY instead: each replica carries a host-side mirror of
the block chains routed to it, and a request goes to the replica with
the deepest block-aligned prefix match, discounted by cache occupancy
(depth × (1 − occupancy)) so a nearly-full cache does not keep
winning traffic it would have to evict its own trie to admit.

Two more behaviors make the router fleet-shaped rather than a toy
hash ring:

* **Session stickiness** — a ``session`` key maps to the replica that
  served it last (bounded LRU), because a conversation's whole history
  is in ONE replica's cache; moving it replays the entire prefix.
* **Drain-free membership** — ``add_replica`` AOT-warms the engine
  BEFORE it enters the ring (the joining replica's first request
  compiles nothing), ``remove_replica`` stops routing to the replica
  FIRST and then drains its in-flight work, so scale-down never fails
  a request that was already admitted.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict

from ..base import MXNetError
from ..telemetry import REGISTRY

__all__ = ["FleetRouter"]

ROUTED = REGISTRY.counter(
    "fleet_router_requests", "requests placed by the fleet router, "
    "labeled by `policy`")
STICKY_HITS = REGISTRY.counter(
    "fleet_router_sticky_hits", "requests routed by session "
    "stickiness (bypassing the scoring policy)")
AFFINITY_BLOCKS = REGISTRY.counter(
    "fleet_router_affinity_blocks", "prefix blocks the chosen replica "
    "already held at routing time (the replay work affinity skipped)")
REPLICAS = REGISTRY.gauge(
    "fleet_replicas", "decode replicas currently in the routing ring "
    "(draining replicas excluded)")

_POLICIES = ("affinity", "least_loaded")


class _MirrorTrie:
    """Host-side mirror of the block chains routed to one replica.

    Same chain structure as ``PagedKVCache``'s trie, but holding no
    blocks — only the router's BELIEF about what the replica cached.
    Bounded: past ``max_blocks`` nodes the oldest routed chain is
    dropped leaf-first, mirroring the cache's own eviction order, so a
    long-running router's belief decays the same way the replica's
    trie does."""

    def __init__(self, block_size, max_blocks):
        self.block_size = int(block_size)
        self.max_blocks = int(max_blocks)
        self._root = {}
        self._count = 0
        self._chains = OrderedDict()       # chain tuple -> True (FIFO)

    def _chain(self, tokens, n_blocks):
        bs = self.block_size
        return [tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(n_blocks)]

    def match(self, tokens):
        """Depth (in blocks) of the deepest mirrored chain matching
        ``tokens`` — capped like ``acquire_prefix`` at
        ``(len - 1) // block_size`` so the score mirrors what the
        replica can actually share."""
        depth = 0
        children = self._root
        for key in self._chain(tokens, (len(tokens) - 1)
                               // self.block_size):
            node = children.get(key)
            if node is None:
                break
            depth += 1
            children = node["children"]
        return depth

    def add(self, tokens):
        keys = self._chain(tokens, len(tokens) // self.block_size)
        if not keys:
            return
        children = self._root
        for key in keys:
            node = children.get(key)
            if node is None:
                node = {"children": {}}
                children[key] = node
                self._count += 1
            children = node["children"]
        self._chains[tuple(keys)] = True
        self._chains.move_to_end(tuple(keys))
        while self._count > self.max_blocks and self._chains:
            old, _ = self._chains.popitem(last=False)
            self._drop(old)

    def _drop(self, keys):
        """Remove one chain's leaf-only nodes (shared ancestors of a
        newer chain survive — they are still live belief)."""
        path = []
        children = self._root
        for key in keys:
            node = children.get(key)
            if node is None:
                break
            path.append((children, key, node))
            children = node["children"]
        for children, key, node in reversed(path):
            if node["children"]:
                break
            del children[key]
            self._count -= 1


class FleetRouter:
    """Prefix-affinity router over named :class:`DecodeEngine`
    replicas.  Thread-safe; every route decision happens under one
    lock plus dirty reads of each engine's scheduler depth (a stale
    load estimate costs placement quality, never correctness)."""

    def __init__(self, policy=None, sticky=None, trie_blocks=None,
                 block_size=None, max_sessions=4096):
        if policy is None:
            policy = os.environ.get("MXNET_FLEET_POLICY", "affinity")
        if policy not in _POLICIES:
            raise MXNetError("MXNET_FLEET_POLICY=%s; use %s"
                             % (policy, "|".join(_POLICIES)))
        if sticky is None:
            sticky = os.environ.get("MXNET_FLEET_STICKY",
                                    "1") not in ("0", "false")
        if trie_blocks is None:
            trie_blocks = int(os.environ.get("MXNET_FLEET_TRIE_BLOCKS",
                                             "4096"))
        self.policy = policy
        self.sticky = bool(sticky)
        self._trie_blocks = int(trie_blocks)
        self._block_size = block_size      # None: adopt 1st replica's
        self._lock = threading.RLock()
        self._replicas = OrderedDict()     # name -> record dict
        self._sessions = OrderedDict()     # session -> replica name
        self._max_sessions = int(max_sessions)

    # -- membership ----------------------------------------------------
    def add_replica(self, name, engine, manifest=None):
        """Enter ``engine`` into the routing ring as ``name``.

        Warmup happens BEFORE ring insertion: ``aot_warm`` replays the
        engine's manifest (or runs geometry warmup) while the replica
        is still invisible to ``route``, so the first routed request
        dispatches a cached program — 0 compiles, the drain-free
        scale-up contract.  Returns the number of programs warmed."""
        with self._lock:
            if name in self._replicas:
                raise MXNetError("fleet: replica %r already registered"
                                 % name)
        warmed = engine.aot_warm(manifest)
        bs = self._block_size or engine.cache.block_size
        if engine.cache.block_size != bs:
            raise MXNetError(
                "fleet: replica %r block_size=%d != fleet block_size=%d"
                " (affinity depths would not be comparable)"
                % (name, engine.cache.block_size, bs))
        with self._lock:
            self._block_size = bs
            self._replicas[name] = {
                "engine": engine,
                "trie": _MirrorTrie(bs, self._trie_blocks),
                "draining": False,
            }
            REPLICAS.set(sum(1 for r in self._replicas.values()
                             if not r["draining"]))
        return warmed

    def remove_replica(self, name, timeout=None):
        """Take ``name`` out of the ring: stop routing to it FIRST,
        then drain its in-flight and queued work, then drop it.
        Returns True when the drain completed inside ``timeout``; the
        replica is removed either way (a stuck drain is the caller's
        signal to stop the engine hard)."""
        with self._lock:
            rec = self._replicas.get(name)
            if rec is None:
                raise MXNetError("fleet: no replica %r" % name)
            rec["draining"] = True
            REPLICAS.set(sum(1 for r in self._replicas.values()
                             if not r["draining"]))
        drained = rec["engine"].drain(timeout=timeout)
        with self._lock:
            self._replicas.pop(name, None)
            self._sessions = OrderedDict(
                (s, n) for s, n in self._sessions.items() if n != name)
        return drained

    def replicas(self):
        with self._lock:
            return [n for n, r in self._replicas.items()
                    if not r["draining"]]

    # -- placement -----------------------------------------------------
    @staticmethod
    def _load(engine):
        # dirty read (no engine lock): len()/iteration under the GIL
        # never sees torn state, and a one-step-stale depth only skews
        # a tie-break
        sched = engine._sched
        return (sum(1 for s in sched.slots if s is not None)
                + len(sched.waiting))

    def route(self, tokens, session=None):
        """Place one prompt; returns ``(name, engine)`` and records
        the placement (mirror trie + session map)."""
        tokens = [int(t) for t in tokens]
        with self._lock:
            live = [(n, r) for n, r in self._replicas.items()
                    if not r["draining"]]
            if not live:
                raise MXNetError("fleet: no live replicas")
            name = None
            if self.sticky and session is not None:
                prev = self._sessions.get(session)
                if prev is not None and any(n == prev for n, _ in live):
                    name = prev
                    STICKY_HITS.inc()
            depth = 0
            if name is None:
                name, depth = self._pick(tokens, live)
            rec = self._replicas[name]
            rec["trie"].add(tokens)
            if session is not None:
                self._sessions[session] = name
                self._sessions.move_to_end(session)
                while len(self._sessions) > self._max_sessions:
                    self._sessions.popitem(last=False)
            ROUTED.labels(policy=self.policy).inc()
            if depth:
                AFFINITY_BLOCKS.inc(depth)
            return name, rec["engine"]

    def _pick(self, tokens, live):
        """Score the live ring.  ``affinity``: depth × (1 − occupancy),
        ties to the lighter replica; ``least_loaded``: scheduler depth
        only (the baseline tests/test_fleet.py compares against)."""
        best, best_key, best_depth = None, None, 0
        for name, rec in live:
            eng = rec["engine"]
            load = self._load(eng)
            if self.policy == "least_loaded":
                key = (load, eng.cache.occupancy)
                depth = 0
            else:
                depth = rec["trie"].match(tokens)
                score = depth * (1.0 - eng.cache.occupancy)
                key = (-score, load, eng.cache.occupancy)
            if best_key is None or key < best_key:
                best, best_key, best_depth = name, key, depth
        return best, best_depth

    def submit(self, tokens, session=None, **kwargs):
        """Route + submit in one call; returns ``(name, handle)``."""
        name, engine = self.route(tokens, session=session)
        return name, engine.submit(tokens, **kwargs)

    # -- observability -------------------------------------------------
    def stats(self):
        with self._lock:
            return {
                "policy": self.policy,
                "sticky": self.sticky,
                "sessions": len(self._sessions),
                "replicas": {
                    n: {
                        "draining": r["draining"],
                        "load": self._load(r["engine"]),
                        "cache_occupancy":
                            round(r["engine"].cache.occupancy, 4),
                        "mirror_blocks": r["trie"]._count,
                    } for n, r in self._replicas.items()
                },
            }
