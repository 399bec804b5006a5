"""Device contexts: ``mx.tpu(i)`` as a first-class context.

Reference parity: include/mxnet/base.h Context (kCPU/kGPU/kCPUPinned) and
python/mxnet/context.py. The TPU-native realization maps a Context onto a
concrete ``jax.Device``; there is no separate storage layer because XLA owns
HBM allocation (reference src/storage/ is replaced by the XLA allocator).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus"]


class Context:
    """Execution device descriptor.

    Parameters
    ----------
    device_type : str
        'cpu', 'tpu', or 'gpu' ('gpu' is accepted for API compatibility and
        resolves to the accelerator backend when one exists).
    device_id : int
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared",
                   6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3,
                   "cpu_shared": 5, "tpu": 6}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- JAX mapping ------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        return _resolve_device(self)

    def __enter__(self):
        if not hasattr(Context._default_ctx, "contexts"):
            Context._default_ctx.contexts = []
        Context._default_ctx.contexts.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.contexts.pop()

    def empty_cache(self):
        # XLA manages HBM; provided for API parity.
        pass


def _accelerators():
    # local_devices: in a multi-process (jax.distributed) world a Context
    # must name a device THIS process owns; identical to jax.devices()
    # when single-process
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _resolve_device(ctx: Context) -> jax.Device:
    if ctx.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        cpus = [d for d in jax.local_devices() if d.platform == "cpu"]
        if not cpus:
            # accelerator-platform processes still carry a host backend;
            # mx.cpu() arrays MUST live there — a fallback to the
            # accelerator would silently turn every data-iterator batch
            # into device traffic. local_devices(backend=...) keeps this
            # process's own cpu device in a jax.distributed world
            # (jax.devices("cpu") would return rank 0's).
            try:
                cpus = jax.local_devices(backend="cpu")
            except RuntimeError:
                cpus = jax.local_devices()  # truly no host backend
        return cpus[min(ctx.device_id, len(cpus) - 1)]
    devs = _accelerators()
    if not devs:
        # an explicit mx.tpu(i)/mx.gpu(i) never means "the host if there
        # is nothing better": ask mx.num_tpus() first, or take
        # default_context(), which may pick the CPU
        raise MXNetError(
            "Context %s: jax sees no accelerator (backend %r); use "
            "mx.cpu() or mx.context.default_context()"
            % (ctx, jax.default_backend()))
    if ctx.device_id >= len(devs):
        raise MXNetError(
            "Context %s out of range: %d device(s) visible" % (ctx, len(devs)))
    return devs[ctx.device_id]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accepted for compatibility; resolves to the accelerator backend."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len([d for d in jax.local_devices() if d.platform != "cpu"])


def num_tpus():
    return num_gpus()


# process-wide fallback installed by test_utils.set_default_context;
# the `with ctx:` stack always takes precedence
_default_override = None


def current_context() -> Context:
    if getattr(Context._default_ctx, "contexts", None):
        return Context._default_ctx.contexts[-1]
    if _default_override is not None:
        return _default_override
    return default_context()


def default_context() -> Context:
    """Default = first accelerator if present else cpu (TPU-first stance)."""
    if any(d.platform != "cpu" for d in jax.local_devices()):
        return Context("tpu", 0)
    return Context("cpu", 0)
