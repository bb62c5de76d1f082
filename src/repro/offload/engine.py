"""Offload engine: where the arena bytes live between forward and backward.

Policies
--------
* ``"device"``       — the pooled arenas stay on device; the backward
                       pass slices segments straight out of them.
* ``"host"``         — every layer's segments move device→host right
                       after that layer's forward stash; the backward
                       walk prefetches them host→device one layer ahead
                       (double-buffered: at most two layers' segments
                       are device-resident at once).
* ``"pinned-paged"`` — like ``"host"`` but pins to the ``pinned_host``
                       memory space and pages the packed-code segment in
                       fixed-size pages (DMA-friendly granularity).

Mechanisms
----------
On platforms that expose a host memory space distinct from the device's
default (TPU/GPU: ``pinned_host``), segments are moved with memory-kind
``jax.device_put`` — asynchronous under XLA, so backward prefetch
overlaps with the previous layer's gradient math.  Everywhere else
(CPU: the default memory *is* unpinned host) the engine falls back to a
**synchronous pure-callback host store**: writes copy the segment into a
Python-side numpy store keyed by ``(forward key, layer tag)`` and return
a ticket; reads take the ticket as an operand, which both enforces
write-before-read ordering inside the XLA program and keeps the writes
from being dead-code-eliminated.  Both mechanisms are bit-preserving, so
``offload="host"`` training matches ``offload="device"`` exactly.

The per-tensor helpers :func:`offload_compressed` /
:func:`fetch_compressed` apply the same callback mechanism to a single
``CompressedTensor`` residual — that is what the transformer ``lax.scan``
path uses (scan stacks residuals across iterations, so its per-layer
residual must be a tiny ticket, not a host-kind array).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend
from repro.core.compressor import CompressedTensor, CompressionConfig
from repro.core.prng import KNUTH_MULT
from repro.offload import arena as ar

POLICIES = ("device", "host", "pinned-paged")

#: Page size (uint32 words) for the "pinned-paged" packed-code paging.
PAGE_WORDS = 1 << 15


def check_policy(policy: str | None) -> str | None:
    if policy is not None and policy not in POLICIES:
        raise ValueError(f"offload={policy!r} not in {POLICIES}")
    return policy


def host_memory_kind(policy: str = "host") -> str | None:
    """The host memory space to offload into, or None if the platform has
    none distinct from the device default (then the callback store is
    used).  ``pinned-paged`` insists on ``pinned_host``; ``host`` takes
    any non-default host kind, preferring pinned."""
    try:
        dev = jax.devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
        default = dev.default_memory().kind
    except Exception:
        return None
    candidates = (("pinned_host",) if policy == "pinned-paged"
                  else ("pinned_host", "unpinned_host"))
    for k in candidates:
        if k in kinds and k != default:
            return k
    return None


def resolve_mechanism(policy: str) -> str:
    check_policy(policy)
    if policy == "device":
        return "device"
    return "memkind" if host_memory_kind(policy) else "callback"


# ----------------------------------------------------- measurement helpers
def measure_live_bytes() -> int:
    """Total bytes of live jax arrays on this host (best-effort gauge the
    ledger is validated against in tests/benchmarks)."""
    return int(sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays()))


def device_memory_stats() -> dict | None:
    """Raw device memory stats (``peak_bytes_in_use`` etc.) where the
    backend exposes them (TPU/GPU); None on CPU.  A TPU without stats is
    an error: the device-peak column would silently read as unmeasured."""
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats is None and dev.platform == "tpu":
        raise RuntimeError(f"{dev.device_kind} reports no memory stats")
    return stats


def device_resident_stash_bytes(plan: ar.StashPlan, policy: str) -> int:
    """Ledger model of *device-resident* stash bytes during backward.

    device: the whole pooled arena.  host / pinned-paged: the
    double-buffered prefetch window — the two largest consecutive layer
    segments (at most two layers are on device at once)."""
    if resolve_mechanism(policy) == "device":
        return plan.total_bytes
    sizes = [lp.nbytes for lp in plan.layers]
    if len(sizes) < 2:
        return sum(sizes)
    return max(a + b for a, b in zip(sizes[:-1], sizes[1:]))


# ------------------------------------------------------ callback host store
# Keyed by (int(forward key), int(tag)).  Entries carry a read refcount so
# the store drains exactly when the backward walk has fetched everything.
_HOST_STORE: dict[tuple[int, int], list[np.ndarray]] = {}
_HOST_REFS: dict[tuple[int, int], int] = {}


def host_store_bytes() -> int:
    return int(sum(a.nbytes for arrs in _HOST_STORE.values() for a in arrs))


def host_store_clear() -> None:
    """Drop leaked entries (tests / aborted differentiations)."""
    _HOST_STORE.clear()
    _HOST_REFS.clear()


def _ticket_of(key: int, tag: int) -> np.uint32:
    return np.uint32((int(key) ^ (tag * int(KNUTH_MULT))) & 0xFFFF_FFFF)


def host_put(key, ticket, tag: int, arrays, n_reads: int = 1):
    """Copy ``arrays`` into the host store under ``(key, tag)``.

    ``ticket`` is the previous put's ticket (or ``key`` itself for the
    first): threading it as an operand serializes the writes and keeps
    them live.  Returns this put's ticket.
    """
    def _cb(k, _t, *arrs):
        kk = (int(k), tag)
        _HOST_STORE[kk] = [np.asarray(a).copy() for a in arrs]
        _HOST_REFS[kk] = n_reads
        return _ticket_of(int(k), tag)

    return jax.pure_callback(
        _cb, jax.ShapeDtypeStruct((), jnp.uint32), key, ticket, *arrays,
        vmap_method="sequential")


def host_get(key, ticket, tag: int, out_shapes):
    """Fetch ``(key, tag)`` back from the host store (synchronous).

    ``ticket`` must (transitively) depend on the matching :func:`host_put`
    so XLA cannot hoist the read above the write.  The entry is freed
    once its refcount drains.
    """
    def _cb(k, _t):
        kk = (int(k), tag)
        arrs = _HOST_STORE[kk]
        out = tuple(a.copy() for a in arrs)
        _HOST_REFS[kk] -= 1
        if _HOST_REFS[kk] <= 0:
            del _HOST_STORE[kk], _HOST_REFS[kk]
        return out

    return jax.pure_callback(_cb, tuple(out_shapes), key, ticket,
                             vmap_method="sequential")


# ----------------------------------------------- per-tensor residual offload
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HostStash:
    """Tiny residual standing in for a host-offloaded ``CompressedTensor``.

    Only the ticket + forward key are traced; shape/dtype/config are
    static aux, so a ``lax.scan`` stacking these across layers carries a
    few words per layer instead of the codes themselves.
    """

    ticket: jnp.ndarray   # () uint32
    key: jnp.ndarray      # () uint32 — the layer seed that keyed the put
    # --- static ---
    shape: tuple[int, ...]
    dtype: str
    cfg: CompressionConfig

    def tree_flatten(self):
        return (self.ticket, self.key), (self.shape, self.dtype, self.cfg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ticket, key = children
        return cls(ticket, key, *aux)


_CT_TAG = 0xC7  # store tag for per-tensor CompressedTensor residuals


def _ct_shapes(shape, cfg: CompressionConfig):
    lp = ar.plan_stashes((tuple(shape),), (cfg,)).layers[0]
    return (jax.ShapeDtypeStruct((lp.n_blocks, lp.words_per_block),
                                 jnp.uint32),
            jax.ShapeDtypeStruct((lp.n_blocks,), jnp.float32),
            jax.ShapeDtypeStruct((lp.n_blocks,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.uint32))


def offload_compressed(ct: CompressedTensor, key) -> HostStash:
    """Move one ``CompressedTensor``'s fields to the callback host store,
    keyed by the (unique-per-stash) ``key`` seed."""
    key = jnp.asarray(key, jnp.uint32)
    ticket = host_put(key, key, _CT_TAG,
                      (ct.packed, ct.zero, ct.rng, ct.rp_seed))
    return HostStash(ticket, key, shape=tuple(ct.shape),
                     dtype=str(jnp.dtype(ct.dtype)), cfg=ct.cfg)


def fetch_compressed(hs: HostStash) -> CompressedTensor:
    cfg = hs.cfg
    packed, zero, rng, rp_seed = host_get(hs.key, hs.ticket, _CT_TAG,
                                          _ct_shapes(hs.shape, cfg))
    impl = backend.route_quant(cfg.impl, cfg.bits, cfg.group_size,
                               cfg.levels())
    return CompressedTensor(packed, zero, rng, rp_seed, shape=hs.shape,
                            dtype=jnp.dtype(hs.dtype), cfg=cfg, impl=impl)


# ------------------------------------------------------- per-tensor writers
class _TensorWriter:
    """Stash kind "tensor": no pooling, no movement — the residual is the
    classic per-layer pytree of ``CompressedTensor`` / raw-f32 / packed
    ReLU-mask leaves, exactly what the pre-arena per-op ``custom_vjp``
    stack saved.  Under the unified engine forward this makes "plain"
    training and arena-routed training two policies of one code path."""

    def __init__(self, plan, policy, key):
        self._segs = [dict() for _ in plan.layers]

    def put_ct(self, li, ct):
        self._segs[li]["ct"] = ct

    def put_raw(self, li, x):
        self._segs[li]["raw"] = x

    def put_mask(self, li, words):
        self._segs[li]["mask"] = words

    def residual(self):
        return tuple(self._segs)


class _TensorReader:
    def __init__(self, plan, policy, res):
        self._segs = res

    def prefetch(self, li):
        pass  # residual leaves are live device arrays already

    def get_ct(self, li):
        return self._segs[li]["ct"]

    def get_raw(self, li):
        return self._segs[li]["raw"]

    def get_mask(self, li):
        return self._segs[li]["mask"]


# ------------------------------------------------------------ arena writers
def _stash_tag(li: int) -> int:
    return 2 * li


def _mask_tag(li: int) -> int:
    return 2 * li + 1


class _DeviceWriter:
    """Policy "device": write straight into the pooled device arenas."""

    def __init__(self, plan, policy, key):
        self.plan = plan
        self.arenas = ar.arena_init(plan)

    def put_ct(self, li, ct):
        self.arenas = ar.stash_write(self.arenas, self.plan, li, ct)

    def put_raw(self, li, x):
        self.arenas = ar.write_raw(self.arenas, self.plan, li, x)

    def put_mask(self, li, words):
        self.arenas = ar.write_mask(self.arenas, self.plan, li, words)

    def residual(self):
        return self.arenas


class _DeviceReader:
    def __init__(self, plan, policy, res):
        self.plan = plan
        self.arenas = res

    def prefetch(self, li):
        pass  # segments are device-resident slices already

    def get_ct(self, li):
        return ar.stash_read(self.arenas, self.plan, li)

    def get_raw(self, li):
        return ar.read_raw(self.arenas, self.plan, li)

    def get_mask(self, li):
        return ar.read_mask(self.arenas, self.plan, li)


class _MemkindWriter:
    """Host memory-space offload via memory-kind ``jax.device_put``.

    Each layer's segments become host-kind arrays right after the layer
    stashes them; the residual is the per-layer dict of host arrays.
    ``pinned-paged`` splits the packed codes into :data:`PAGE_WORDS`
    pages so prefetch granularity matches DMA-friendly page sizes.
    """

    def __init__(self, plan, policy, key):
        self.plan = plan
        self.paged = policy == "pinned-paged"
        kind = host_memory_kind(policy)
        dev = jax.devices()[0]
        self._host = jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)
        self.segs = {}

    def _off(self, x):
        return jax.device_put(x, self._host)

    def _off_paged(self, flat):
        if not self.paged or flat.size <= PAGE_WORDS:
            return (self._off(flat),)
        return tuple(self._off(flat[i:i + PAGE_WORDS])
                     for i in range(0, flat.size, PAGE_WORDS))

    def put_ct(self, li, ct):
        self.segs[li] = {"packed": self._off_paged(ct.packed.reshape(-1)),
                         "zero": self._off(ct.zero),
                         "rng": self._off(ct.rng),
                         "rp_seed": self._off(ct.rp_seed)}

    def put_raw(self, li, x):
        self.segs[li] = {"raw": self._off(x)}

    def put_mask(self, li, words):
        self.segs[li]["mask"] = self._off(words)

    def residual(self):
        return tuple(self.segs[li] for li in sorted(self.segs))


class _MemkindReader:
    def __init__(self, plan, policy, res):
        self.plan = plan
        dev = jax.devices()[0]
        self._dev = jax.sharding.SingleDeviceSharding(dev)
        self.segs = dict(enumerate(res))
        self._cache = {}

    def _pop(self, li, field):
        # drop the reader's reference once the field is consumed so the
        # double-buffer claim (≤ 2 layers device-resident) holds even in
        # eager backward walks, where this dict would otherwise pin every
        # fetched copy until the walk ends
        entry = self._cache[li]
        val = entry.pop(field)
        if not entry:
            del self._cache[li]
        return val

    def _fetch(self, li):
        # one device_put per segment — issued when ``prefetch`` runs, one
        # layer ahead of use, so the host→device copy overlaps the
        # previous layer's gradient math under XLA async dispatch
        back = {k: (tuple(jax.device_put(p, self._dev) for p in v)
                    if isinstance(v, tuple)
                    else jax.device_put(v, self._dev))
                for k, v in self.segs[li].items()}
        if "packed" in back:
            back["packed"] = jnp.concatenate(back["packed"])
        return back

    def prefetch(self, li):
        if li not in self._cache:
            self._cache[li] = self._fetch(li)

    def get_ct(self, li):
        lp = self.plan.layers[li]
        self.prefetch(li)
        cfg = lp.cfg
        impl = backend.route_quant(cfg.impl, cfg.bits, cfg.group_size,
                                   cfg.levels())
        packed, zero, rng, rp_seed = (self._pop(li, f) for f in
                                      ("packed", "zero", "rng", "rp_seed"))
        return CompressedTensor(
            packed=packed.reshape(lp.n_blocks, lp.words_per_block),
            zero=zero, rng=rng, rp_seed=rp_seed,
            shape=lp.shape, dtype=jnp.dtype(self.plan.dtype), cfg=cfg,
            impl=impl)

    def get_raw(self, li):
        lp = self.plan.layers[li]
        self.prefetch(li)
        return self._pop(li, "raw").reshape(lp.shape).astype(
            jnp.dtype(self.plan.dtype))

    def get_mask(self, li):
        lp = self.plan.layers[li]
        self.prefetch(li)
        return self._pop(li, "mask").reshape(1, lp.mask.size)


class _CallbackWriter:
    """Synchronous pure-callback host store (the no-host-memory-space
    fallback).  Residual is a single chained ticket + the forward key."""

    def __init__(self, plan, policy, key):
        self.plan = plan
        self.key = jnp.asarray(key, jnp.uint32)
        self.ticket = self.key

    def put_ct(self, li, ct):
        self.ticket = host_put(self.key, self.ticket, _stash_tag(li),
                               (ct.packed, ct.zero, ct.rng, ct.rp_seed))

    def put_raw(self, li, x):
        self.ticket = host_put(self.key, self.ticket, _stash_tag(li), (x,))

    def put_mask(self, li, words):
        self.ticket = host_put(self.key, self.ticket, _mask_tag(li), (words,))

    def residual(self):
        return (self.ticket, self.key)


class _CallbackReader:
    def __init__(self, plan, policy, res):
        self.plan = plan
        self.ticket, self.key = res
        self._cache = {}

    def prefetch(self, li):
        if li in self._cache:
            return
        lp = self.plan.layers[li]
        out = {}
        if lp.packed is not None:
            out["ct"] = host_get(
                self.key, self.ticket, _stash_tag(li),
                (jax.ShapeDtypeStruct((lp.n_blocks, lp.words_per_block),
                                      jnp.uint32),
                 jax.ShapeDtypeStruct((lp.n_blocks,), jnp.float32),
                 jax.ShapeDtypeStruct((lp.n_blocks,), jnp.float32),
                 jax.ShapeDtypeStruct((), jnp.uint32)))
        else:
            out["raw"] = host_get(
                self.key, self.ticket, _stash_tag(li),
                (jax.ShapeDtypeStruct(lp.shape, jnp.float32),))[0]
        if lp.mask is not None:
            out["mask"] = host_get(
                self.key, self.ticket, _mask_tag(li),
                (jax.ShapeDtypeStruct((1, lp.mask.size), jnp.uint32),))[0]
        self._cache[li] = out

    def _pop(self, li, field):
        # consumed fields leave the cache (see _MemkindReader._pop)
        entry = self._cache[li]
        val = entry.pop(field)
        if not entry:
            del self._cache[li]
        return val

    def get_ct(self, li):
        self.prefetch(li)
        lp = self.plan.layers[li]
        cfg = lp.cfg
        packed, zero, rng, rp_seed = self._pop(li, "ct")
        impl = backend.route_quant(cfg.impl, cfg.bits, cfg.group_size,
                                   cfg.levels())
        return CompressedTensor(packed, zero, rng, rp_seed, shape=lp.shape,
                                dtype=jnp.dtype(self.plan.dtype), cfg=cfg,
                                impl=impl)

    def get_raw(self, li):
        self.prefetch(li)
        return self._pop(li, "raw").astype(jnp.dtype(self.plan.dtype))

    def get_mask(self, li):
        self.prefetch(li)
        return self._pop(li, "mask")


_WRITERS = {"tensor": _TensorWriter, "device": _DeviceWriter,
            "memkind": _MemkindWriter, "callback": _CallbackWriter}
_READERS = {"tensor": _TensorReader, "device": _DeviceReader,
            "memkind": _MemkindReader, "callback": _CallbackReader}


def resolve_stash(kind: str, placement: str) -> str:
    """Mechanism for an engine :class:`~repro.engine.plan.StashPolicy`:
    kind "tensor" is its own mechanism (placement is always "device");
    kind "arena" resolves the placement policy as before."""
    if kind == "tensor":
        return "tensor"
    return resolve_mechanism(placement)


def make_writer(plan: ar.StashPlan, policy: str, key, *,
                kind: str = "arena"):
    """Trace-time stash writer for one forward pass.

    ``key`` is a uint32 scalar unique to this forward (the base SR seed) —
    the callback store keys entries by it, so vmapped/scanned forwards
    with distinct seeds never collide.  ``kind`` selects per-tensor vs
    pooled-arena storage (the engine's stash-policy axis); the legacy
    arena-only callers omit it.
    """
    return _WRITERS[resolve_stash(kind, policy)](plan, policy, key)


def make_reader(plan: ar.StashPlan, policy: str, residual, *,
                kind: str = "arena"):
    """Backward-walk reader over a writer's residual.  Call
    ``prefetch(li - 1)`` before consuming layer ``li`` to keep the
    host→device copy one layer ahead (double-buffered)."""
    return _READERS[resolve_stash(kind, policy)](plan, policy, residual)
