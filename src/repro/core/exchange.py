"""Pluggable gradient-exchange protocols — the paper's §III-B as an API.

The exchange layer (RabbitMQ mailboxes, QSGD compression, sync/async
consumption) is the paper's core contribution, so it is a first-class,
registry-backed abstraction instead of a string-dispatched ``if/elif``
chain. One :class:`ExchangeProtocol` subclass implements BOTH execution
paths plus its wire-byte accounting:

* **device path** — :meth:`~ExchangeProtocol.combine` runs inside the
  ``shard_map`` manual region of the TPU train step; peers are mesh-axis
  slices and the mailbox is an all-gathered register bank carried in the
  train state.
* **host path** — :meth:`~ExchangeProtocol.host_encode` /
  :meth:`~ExchangeProtocol.host_decode` serialize one peer's gradient for
  the :class:`~repro.core.mailbox.HostMailbox` used by the
  ``LocalP2PCluster`` discrete-event simulator.
* **accounting** — :meth:`~ExchangeProtocol.wire_bytes_per_edge` reports
  the payload crossing one overlay edge; :meth:`~ExchangeProtocol.wire_bytes`
  scales it by the peer's graph degree (``P - 1`` on the full mesh);
  :class:`repro.core.cost.CommCost` turns that into wire seconds / dollars.

The peer overlay itself (full / ring / gossip-k / hierarchical) is the
:class:`repro.core.graph.PeerGraph` carried in :class:`ExchangeContext`:
sync protocols mix with the graph's Metropolis–Hastings weights instead
of the global mean whenever ``ctx.mixing`` is set (it is ``None`` on the
full graph, which keeps the legacy arithmetic bit-exact).

Adding a protocol is one registered class::

    @register_exchange("my_protocol")
    class MyProtocol(ExchangeProtocol):
        def combine(self, grads, ctx, *, key=None, state=None):
            ...
            return averaged, state

``Topology(exchange="my_protocol")`` then works everywhere — the TPU step
builder, the host cluster, ``launch/train.py`` CLI and the benchmarks all
resolve names through this registry.
"""
from __future__ import annotations

import abc
import dataclasses
from dataclasses import dataclass
from typing import Any, ClassVar, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import compression as C
from repro.core import robust as R
from repro.core.shard import ShardPlan


@dataclass(frozen=True)
class ExchangeContext:
    """Everything a protocol needs besides the gradients themselves.

    ``axis`` is the peer mesh axis (name or tuple of names) for device
    collectives; None on the host path, where peers are Python objects and
    the mailbox delivers payloads instead of ``all_gather``.

    ``graph`` / ``mixing`` carry the peer overlay (see
    ``repro.core.graph``): ``graph`` is the resolved :class:`PeerGraph`
    and ``mixing`` its Metropolis–Hastings matrix ``W`` as an fp32
    ``(P, P)`` array — or ``None`` for the full graph, where the weights
    are uniformly ``1/P`` and protocols keep the legacy (bit-exact)
    global-mean arithmetic. Sync protocols generalize the mean to
    ``x_r <- sum_j W[r, j] x_j`` when ``mixing`` is set.
    """

    axis: Any = None
    num_peers: int = 1
    wire_dtype: Any = jnp.float32
    qsgd: Optional[C.QSGDConfig] = None
    topk_frac: float = 0.01
    topk_impl: str = "jnp"  # "jnp" (lax.top_k oracle) | "kernel" (Pallas)
    staleness: int = 1
    graph: Any = None  # resolved repro.core.graph.PeerGraph, or None
    mixing: Any = None  # (P, P) fp32 MH matrix; None => uniform 1/P (full)
    # robust-aggregation knobs (see repro.core.robust); a parameterized
    # protocol spec ("trimmed_mean:0.25", "krum:3") overrides these
    trim_frac: float = 0.0  # trimmed_mean: fraction dropped from EACH end
    krum_m: int = 1  # krum: multi-Krum selection count
    krum_f: Optional[int] = None  # krum: assumed attackers (None = max tolerable)
    robust_clip: float = 0.0  # >0: per-peer norm clip before robust combine

    def __post_init__(self):
        # A graph sized for a different peer count silently mis-mixes (the
        # MH matrix rows no longer line up with mesh ranks) — refuse here,
        # at construction, with an actionable message.
        gp = getattr(self.graph, "num_peers", None)
        if gp is not None and gp != self.num_peers:
            raise ValueError(
                f"ExchangeContext(num_peers={self.num_peers}) does not match "
                f"its overlay graph, which was built for {gp} peers "
                f"({self.graph.describe()}); resolve the graph for the "
                f"actual peer count (get_graph(spec, num_peers))"
            )

    @property
    def degree(self) -> float:
        """Mean neighbor count of one peer — (P-1) when no graph is set."""
        if self.graph is not None:
            return float(self.graph.mean_degree)
        return float(max(self.num_peers - 1, 0))

    def mix_row(self):
        """This peer's mixing weights ``W[r]`` inside the manual region."""
        r = lax.axis_index(self.axis)
        return jnp.asarray(self.mixing, jnp.float32)[r], r


class ExchangeProtocol(abc.ABC):
    """Abstract gradient-exchange protocol (see module docstring)."""

    name: ClassVar[str] = "?"  # set by @register_exchange
    is_async: ClassVar[bool] = False  # consumes stale mailbox state
    requires_key: ClassVar[bool] = False  # needs an rng key (stochastic codec)
    decomposes_per_edge: ClassVar[bool] = True  # False: fused collective
    requires_full_graph: ClassVar[bool] = False  # True: refuses sparse overlays
    sharded: ClassVar[bool] = False  # True: shards, not pytrees, on the wire
    lossy: ClassVar[bool] = False  # True: codec drops information (EF applies)
    hierarchical: ClassVar[bool] = False  # True: multi-level tree reduce
    scoped: ClassVar[bool] = False  # True: opens p2p.encode/gather/decode itself

    # -- device path --------------------------------------------------------
    def init_state(self, grads_like, ctx: ExchangeContext):
        """Per-protocol carried state (e.g. the async mailbox); None if none."""
        return None

    @abc.abstractmethod
    def combine(self, grads, ctx: ExchangeContext, *, key=None, state=None):
        """(grads, state) -> (averaged_grads fp32, new_state).

        Runs inside the manual region; sync protocols pass ``state``
        through untouched.
        """

    def combine_ef(self, grads, ctx: ExchangeContext, *, key=None, state=None):
        """Error-feedback variant: -> (averaged, local_image, new_state).

        ``local_image`` is the decoded image of THIS peer's shipped
        contribution — what the rest of the swarm actually received from
        us. EF-SGD accumulates ``residual = grads - local_image`` and adds
        it back before the next encode. Lossless protocols ship ``grads``
        verbatim, so the default keeps the residual identically zero;
        lossy codecs (qsgd, topk) override.
        """
        avg, state = self.combine(grads, ctx, key=key, state=state)
        return avg, grads, state

    # -- host path -----------------------------------------------------------
    def host_encode(self, grads, ctx: ExchangeContext, *, key=None):
        """One peer's gradient -> (wire payload, wire bytes)."""
        wire = jax.tree.map(lambda g: g.astype(ctx.wire_dtype), grads)
        return wire, _tree_bytes(wire)

    def host_decode(self, payload, grads_like, ctx: ExchangeContext):
        """Wire payload -> this peer's dense fp32 gradient contribution."""
        return jax.tree.map(lambda g: g.astype(jnp.float32), payload)

    def host_combine(self, grads_peers, rank: int, ctx: ExchangeContext):
        """Protocol-specific host-path aggregation, or ``None`` for the
        default (graph-weighted mean) arithmetic.

        ``grads_peers`` maps contributor rank -> decoded fp32 gradient
        (always including ``rank``'s own). Protocols whose estimator is
        NOT a weighted mean (the robust family) override this; the
        cluster's ``_update`` dispatches here first and falls back to the
        legacy Metropolis–Hastings / plain-mean path on ``None``.
        """
        return None

    # -- accounting ----------------------------------------------------------
    def wire_bytes_per_edge(self, grads_like, ctx: ExchangeContext) -> int:
        """Payload bytes crossing ONE graph edge (one peer -> one neighbor).

        This is the unit the overlay-aware accounting is built from:
        compression/sparsification protocols override it, the degree
        scaling lives in :meth:`wire_bytes`.
        """
        itemsize = jnp.dtype(ctx.wire_dtype).itemsize
        return sum(int(np.prod(x.shape)) * itemsize for x in jax.tree.leaves(grads_like))

    def wire_bytes(self, grads_like, ctx: ExchangeContext) -> int:
        """Total bytes one peer moves per step: per-edge payload x degree.

        Degree comes from the overlay graph in ``ctx`` (``P - 1`` for the
        full mesh), so sparse topologies (ring: 2, gossip: k) show their
        O(degree) per-peer traffic while full-mesh grows O(P). Fused
        collectives that don't decompose into edges override this whole
        method (see ``psum_mean``).
        """
        return int(round(self.wire_bytes_per_edge(grads_like, ctx) * ctx.degree))

    def host_wire_bytes(self, grads_like, ctx: ExchangeContext) -> int:
        """Bytes one peer PUBLISHES on the host mailbox path per step.

        The mailbox is a latest-wins register: a peer publishes its
        payload once and each neighbor pays the download separately
        (charged per consume by ``HostMailbox.download_time_s``), so the
        publish figure is one edge-payload regardless of degree.
        """
        return self.wire_bytes_per_edge(grads_like, ctx)

    def describe(self) -> str:
        return (self.__doc__ or "").strip().splitlines()[0] if self.__doc__ else ""


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type[ExchangeProtocol]] = {}


def register_exchange(name: str):
    """Class decorator: make a protocol reachable as ``Topology(exchange=name)``."""

    def deco(cls: Type[ExchangeProtocol]) -> Type[ExchangeProtocol]:
        if not issubclass(cls, ExchangeProtocol):
            raise TypeError(f"{cls!r} must subclass ExchangeProtocol")
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available_exchanges() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_exchange(spec: str) -> ExchangeProtocol:
    """Resolve a protocol spec: a registered name with an optional
    parameter suffix, mirroring the graph registry — ``"allgather_mean"``,
    ``"trimmed_mean:0.25"``, ``"krum:3"``. The parameter overrides the
    matching :class:`ExchangeContext` knob for this instance."""
    name, _, arg = str(spec).partition(":")
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown exchange protocol {spec!r}; registered protocols: "
            f"{', '.join(available_exchanges())}"
        ) from None
    if not arg:
        return cls()
    try:
        return cls(param=arg)
    except TypeError:
        raise ValueError(
            f"exchange protocol {name!r} does not take a ':' parameter "
            f"(got {spec!r})"
        ) from None


# ---------------------------------------------------------------------------
# Registered protocols
# ---------------------------------------------------------------------------


@register_exchange("allgather_mean")
class AllGatherMean(ExchangeProtocol):
    """Paper-faithful Algorithm 1: publish to own queue, consume all, average.

    Device image: ``all_gather`` over the peer axis + local mean — the
    gather IS the synchronization barrier (§III-B.6). Under a sparse
    overlay (``ctx.mixing`` set) the mean generalizes to the
    Metropolis–Hastings neighbor mix ``W[r] @ bank``; on the full graph
    ``W`` is uniform ``1/P`` and the legacy mean path is kept bit-exact.
    """

    def combine(self, grads, ctx, *, key=None, state=None):
        bank = jax.tree.map(
            lambda g: lax.all_gather(g.astype(ctx.wire_dtype), ctx.axis), grads
        )
        if ctx.mixing is None:
            avg = jax.tree.map(lambda b: b.astype(jnp.float32).mean(axis=0), bank)
        else:
            w, _ = ctx.mix_row()
            avg = jax.tree.map(
                lambda b: jnp.tensordot(w, b.astype(jnp.float32), axes=(0, 0)),
                bank,
            )
        return avg, state


@register_exchange("psum_mean")
class PsumMean(ExchangeProtocol):
    """Beyond-paper optimized sync exchange: one fused all-reduce.

    Mathematically identical to allgather_mean, strictly less traffic (no
    P-way buffer materialization); a ring all-reduce moves
    ``2 (P-1)/P x raw`` bytes per peer. The fused reduction is inherently
    global, so this protocol only supports the full overlay graph.
    """

    decomposes_per_edge = False

    def combine(self, grads, ctx, *, key=None, state=None):
        if ctx.mixing is not None:
            raise ValueError(
                "psum_mean is a fused global all-reduce and only supports "
                "graph='full'; use allgather_mean (or qsgd/topk) for sparse "
                "overlays"
            )
        avg = jax.tree.map(
            lambda g: lax.pmean(g.astype(ctx.wire_dtype), ctx.axis).astype(jnp.float32),
            grads,
        )
        return avg, state

    def wire_bytes(self, grads_like, ctx) -> int:
        # Fused ring all-reduce: does not decompose into per-edge messages.
        raw = self.wire_bytes_per_edge(grads_like, ctx)
        P_ = max(ctx.num_peers, 1)
        return int(raw * 2 * (P_ - 1) / P_)


@register_exchange("qsgd")
class QSGDExchange(ExchangeProtocol):
    """QSGD-compressed exchange (paper §III-B.4): int8 levels + bucket norms.

    Stochastic quantization keeps the estimator unbiased; 8 + 32/bucket
    bits/element on the wire vs 32 uncompressed.
    """

    requires_key = True
    lossy = True
    scoped = True

    def _cfg(self, ctx) -> C.QSGDConfig:
        return ctx.qsgd or C.QSGDConfig()

    def _combine(self, grads, ctx, *, key, want_local: bool):
        """Shared device path. The decode side is the FUSED formulation
        ``dequant_reduce`` (one pass over all P gathered int8 banks,
        mixing-weighted) — Pallas kernel when ``cfg.impl == "kernel"``,
        jnp reference otherwise. Returns (avg, local_image-or-None). Each
        stage runs in its layer's scope: ``p2p.encode``, ``p2p.gather``,
        ``p2p.decode``.
        """
        qcfg = self._cfg(ctx)
        if key is None:
            raise ValueError("qsgd exchange requires an rng key")
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        with jax.named_scope("p2p.encode"):
            key = jax.random.fold_in(key, lax.axis_index(ctx.axis))
            keys = jax.random.split(key, len(leaves))
        with jax.named_scope("p2p.decode"):
            w = None if ctx.mixing is None else ctx.mix_row()[0]

        def leaf(g, k):
            with jax.named_scope("p2p.encode"):
                payload = C.quantize(g, k, qcfg)  # routes cfg.impl for encode
            with jax.named_scope("p2p.gather"):
                lev = lax.all_gather(payload["levels"], ctx.axis)  # (P, nb, B)
                nrm = lax.all_gather(payload["norms"], ctx.axis)  # (P, nb)
            with jax.named_scope("p2p.decode"):
                P_ = lev.shape[0]
                wrow = jnp.full((P_,), 1.0 / P_, jnp.float32) if w is None else w
                flat = C.dequant_reduce(lev, nrm, wrow, qcfg).reshape(-1)
                avg = flat[: g.size].reshape(g.shape)
                if not want_local:
                    return avg, None
                local = C.dequantize(payload, qcfg).reshape(g.shape)
            return avg, local

        pairs = [leaf(g, k) for g, k in zip(leaves, keys)]
        avg = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
        if not want_local:
            return avg, None
        local = jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs])
        return avg, local

    def combine(self, grads, ctx, *, key=None, state=None):
        avg, _ = self._combine(grads, ctx, key=key, want_local=False)
        return avg, state

    def combine_ef(self, grads, ctx, *, key=None, state=None):
        avg, local = self._combine(grads, ctx, key=key, want_local=True)
        return avg, local, state

    def host_encode(self, grads, ctx, *, key=None):
        if key is None:
            raise ValueError("qsgd exchange requires an rng key")
        payload, _ = C.quantize_tree(grads, key, self._cfg(ctx))
        return payload, C.payload_bytes(payload)

    def host_decode(self, payload, grads_like, ctx):
        dense = C.dequantize_tree(payload, self._cfg(ctx))
        return jax.tree.map(lambda d, g: d.reshape(g.shape), dense, grads_like)

    def wire_bytes_per_edge(self, grads_like, ctx) -> int:
        qcfg = self._cfg(ctx)
        total = 0
        for x in jax.tree.leaves(grads_like):
            nb = -(-int(np.prod(x.shape)) // qcfg.bucket)  # ceil: padded buckets
            total += nb * qcfg.bucket * 1 + nb * 4  # int8 levels + fp32 norms
        return total


@register_exchange("topk")
class TopKExchange(ExchangeProtocol):
    """Top-k sparsified exchange: each peer ships only its ``topk_frac``
    largest-magnitude gradient entries (values + int32 indices); receivers
    scatter-add and average. Deterministic, biased towards large
    coordinates — the registry's proof-of-extension protocol.

    ``ctx.topk_impl`` picks the select/scatter implementation:
    ``"jnp"`` is the ``lax.top_k`` + ``.at[].add`` oracle; ``"kernel"``
    runs the Pallas bisection-threshold select+pack encoder
    (``repro.kernels.topk``); both decode with XLA's scatter-add. On exact
    magnitude ties at the k-th position the two may pick different (equal
    magnitude) coordinates; otherwise they select identically.
    """

    lossy = True
    scoped = True

    @staticmethod
    def _k(n: int, frac: float) -> int:
        return max(1, min(n, int(round(n * frac))))

    @staticmethod
    def _select(flat, k: int, ctx):
        """(k,) f32 values + (k,) int32 indices of the k largest |flat|."""
        from repro.kernels import ops as kops
        from repro.kernels import ref as kref

        if ctx.topk_impl == "kernel":
            return kops.topk_select_pack(flat, k)
        return kref.topk_select_ref(flat, k)

    @staticmethod
    def _scatter(vbank, ibank, wrow, n: int, ctx):
        """Sparse decode-reduce: (P, k) banks -> weighted dense (n,)."""
        from repro.kernels import ops as kops
        from repro.kernels import ref as kref

        if ctx.topk_impl == "kernel":
            return kops.topk_scatter_accum(vbank, ibank, wrow, n)
        return kref.topk_scatter_ref(vbank, ibank, wrow, n)

    def _combine(self, grads, ctx, *, want_local: bool):
        frac = ctx.topk_frac
        with jax.named_scope("p2p.decode"):
            w = None if ctx.mixing is None else ctx.mix_row()[0]

        def leaf(g):
            with jax.named_scope("p2p.encode"):
                flat = g.astype(jnp.float32).reshape(-1)
                k = self._k(flat.size, frac)
                vals, idx = self._select(flat, k, ctx)
            with jax.named_scope("p2p.gather"):
                vbank = lax.all_gather(vals.astype(ctx.wire_dtype), ctx.axis)  # (P, k)
                ibank = lax.all_gather(idx, ctx.axis)  # (P, k)
            with jax.named_scope("p2p.decode"):
                P_ = vbank.shape[0]
                wrow = jnp.full((P_,), 1.0 / P_, jnp.float32) if w is None else w
                dense = self._scatter(
                    vbank.astype(jnp.float32), ibank, wrow, flat.size, ctx
                )
                avg = dense.reshape(g.shape)
                if not want_local:
                    return avg, None
                local = self._scatter(
                    vals[None].astype(jnp.float32),
                    idx[None],
                    jnp.ones((1,), jnp.float32),
                    flat.size,
                    ctx,
                ).reshape(g.shape)
            return avg, local

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        pairs = [leaf(g) for g in leaves]
        avg = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
        if not want_local:
            return avg, None
        local = jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs])
        return avg, local

    def combine(self, grads, ctx, *, key=None, state=None):
        avg, _ = self._combine(grads, ctx, want_local=False)
        return avg, state

    def combine_ef(self, grads, ctx, *, key=None, state=None):
        avg, local = self._combine(grads, ctx, want_local=True)
        return avg, local, state

    def host_encode(self, grads, ctx, *, key=None):
        frac = ctx.topk_frac
        itemsize = jnp.dtype(ctx.wire_dtype).itemsize
        nbytes = 0
        payload = []
        for g in jax.tree.leaves(grads):
            flat = jnp.asarray(g, jnp.float32).reshape(-1)
            k = self._k(flat.size, frac)
            vals, idx = self._select(flat, k, ctx)
            payload.append(
                {
                    "values": vals.astype(ctx.wire_dtype),
                    "idx": idx,
                    "shape": np.asarray(g.shape, np.int64),
                }
            )
            nbytes += k * (itemsize + 4)
        treedef = jax.tree_util.tree_structure(grads)
        return jax.tree_util.tree_unflatten(treedef, payload), nbytes

    def host_decode(self, payload, grads_like, ctx):
        def leaf(p, g):
            n = int(np.prod(p["shape"])) if len(p["shape"]) else 1
            dense = self._scatter(
                p["values"].astype(jnp.float32)[None],
                jnp.asarray(p["idx"])[None],
                jnp.ones((1,), jnp.float32),
                n,
                ctx,
            )
            return dense.reshape(tuple(int(d) for d in p["shape"]))

        is_payload = lambda x: isinstance(x, dict) and "values" in x
        return jax.tree.map(leaf, payload, grads_like, is_leaf=is_payload)

    def wire_bytes_per_edge(self, grads_like, ctx) -> int:
        itemsize = jnp.dtype(ctx.wire_dtype).itemsize
        return sum(
            self._k(int(np.prod(x.shape)), ctx.topk_frac) * (itemsize + 4)
            for x in jax.tree.leaves(grads_like)
        )


@register_exchange("async")
class StalenessMailbox(ExchangeProtocol):
    """Asynchronous staleness-K mailbox exchange (paper's "latest available
    gradient", generalized). The carried state is a ring of the last K
    published register banks, leaves shaped ``(K, P, *grad)``; peers consume
    the bank published K steps ago (K=1 == the paper's staleness-1) while
    their own contribution is always fresh.
    """

    is_async = True

    def init_state(self, grads_like, ctx):
        K = max(1, int(ctx.staleness))
        return jax.tree.map(
            lambda g: jnp.zeros((K, ctx.num_peers) + tuple(g.shape), jnp.float32),
            grads_like,
        )

    def combine(self, grads, ctx, *, key=None, state=None):
        if state is None:
            raise ValueError(
                "async exchange requires mailbox state; initialize the train "
                "state with init_mailbox(...) or ExchangeProtocol.init_state(...)"
            )
        r = lax.axis_index(ctx.axis)
        # Gather in the wire dtype (so byte accounting matches what ships),
        # store the ring in fp32 for the staleness arithmetic.
        fresh = jax.tree.map(
            lambda g: lax.all_gather(g.astype(ctx.wire_dtype), ctx.axis)
            .astype(jnp.float32),
            grads,
        )

        w = None if ctx.mixing is None else jnp.asarray(ctx.mixing, jnp.float32)[r]

        def comb(ring, g):
            oldest = ring[0]  # bank published K steps ago
            if w is None:
                nP = oldest.shape[0]
                others = oldest.sum(0) - oldest[r]
                return (others + g.astype(jnp.float32)) / nP
            # neighbor-weighted stale mix; own contribution is always fresh
            others = jnp.tensordot(w, oldest, axes=(0, 0)) - w[r] * oldest[r]
            return others + w[r] * g.astype(jnp.float32)

        avg = jax.tree.map(comb, state, grads)
        new_state = jax.tree.map(
            lambda ring, f: jnp.concatenate([ring[1:], f[None]], axis=0), state, fresh
        )
        return avg, new_state


@register_exchange("reduce_scatter")
class ReduceScatterMean(ExchangeProtocol):
    """Sharded mean: ring reduce-scatter + allgather over contiguous shards.

    The LambdaML/SPIRT communication pattern brought into the registry:
    the gradient pytree flattens into one buffer (:class:`ShardPlan`),
    peer ``r`` ends up owning the fully-reduced shard ``r`` after ``P-1``
    ``ppermute`` ring hops, divides by ``P``, and an allgather of the
    owned shards reconstructs the global mean everywhere. Shards — not
    whole pytrees — are the unit of exchange, so the per-edge payload is
    ``model / P`` and each peer's aggregation work is ``O(model / P)``
    per contribution instead of ``O(model)``.

    Bit-math: the reduced buffer equals the peer mean (summation order
    differs from ``mean(axis=0)`` only by float re-association), so the
    full-graph result matches ``allgather_mean`` to ~1e-6 — the safety
    rail the equivalence tests pin down on device and host. The shard
    layout is inherently global (shard ``r`` aggregates over ALL peers),
    so sparse overlays are refused, like ``psum_mean``.

    Host image: peers publish shard-addressed *pieces* to the mailbox,
    each owner aggregates only its shard and re-broadcasts it — P
    aggregators that run as parallel serverless invocations (see
    ``ServerlessExecutor.simulate_aggregation``), with Lambda memory
    sized from shard bytes instead of model bytes.
    """

    requires_full_graph = True
    sharded = True

    def plan(self, grads_like, ctx: ExchangeContext) -> ShardPlan:
        """The shard layout for this peer count — one shard per peer."""
        return ShardPlan.for_tree(grads_like, max(int(ctx.num_peers), 1))

    def _check_full(self, ctx: ExchangeContext):
        if ctx.mixing is not None:
            raise ValueError(
                "reduce_scatter shards are aggregated over ALL peers and "
                "the protocol only supports graph='full'; use "
                "allgather_mean (or qsgd/topk) for sparse overlays"
            )

    # -- device path ---------------------------------------------------------
    def combine(self, grads, ctx, *, key=None, state=None):
        self._check_full(ctx)
        P_ = int(ctx.num_peers)
        plan = self.plan(grads, ctx)
        buf = plan.shards(grads).astype(jnp.float32)  # (P, S)
        if P_ == 1:
            return plan.unflatten(buf), state
        r = lax.axis_index(ctx.axis)
        perm = [(i, (i + 1) % P_) for i in range(P_)]

        def take(c):
            return lax.dynamic_index_in_dim(buf, c, axis=0, keepdims=False)

        # Ring reduce-scatter: after P-1 hops rank r holds sum_j shard_r(j).
        # Invariant: before hop s, the carried partial covers shard
        # (r - 1 - s) mod P over peers {r-s, ..., r}; each hop forwards the
        # partial one rank clockwise and the receiver adds its own piece.
        acc = take(jnp.mod(r - 1, P_))
        for s in range(P_ - 1):
            acc = lax.ppermute(acc.astype(ctx.wire_dtype), ctx.axis, perm)
            acc = acc.astype(jnp.float32) + take(jnp.mod(r - 2 - s, P_))
        own = acc / P_  # rank r owns the fully-reduced (mean) shard r
        # Allgather phase: rank j contributes reduced shard j, so the
        # gathered bank rows are already in shard-index order.
        bank = lax.all_gather(own.astype(ctx.wire_dtype), ctx.axis)
        return plan.unflatten(bank.astype(jnp.float32)), state

    # -- host path (shard-addressed) -----------------------------------------
    def host_encode_shard(self, shard_values, ctx: ExchangeContext, *, key=None):
        """One shard row -> (wire payload, wire bytes)."""
        wire = jnp.asarray(shard_values).astype(ctx.wire_dtype)
        return wire, int(wire.size * jnp.dtype(ctx.wire_dtype).itemsize)

    def host_decode_shard(self, payload, ctx: ExchangeContext):
        """Wire shard payload -> fp32 shard row."""
        return jnp.asarray(payload).astype(jnp.float32)

    # -- accounting ----------------------------------------------------------
    def wire_bytes_per_edge(self, grads_like, ctx) -> int:
        """One shard crosses one edge: ``model / P`` bytes — the payload
        figure that shrinks as 1/P while dense protocols stay flat."""
        return self.plan(grads_like, ctx).shard_bytes(ctx.wire_dtype)

    def wire_bytes(self, grads_like, ctx) -> int:
        """Ring reduce-scatter + allgather: (P-1) shard sends per phase."""
        P_ = max(int(ctx.num_peers), 1)
        return 2 * (P_ - 1) * self.wire_bytes_per_edge(grads_like, ctx)

    def host_wire_bytes(self, grads_like, ctx) -> int:
        """Mailbox publishes per step: P-1 shard pieces (one per other
        owner) + this peer's re-broadcast aggregated shard."""
        P_ = max(int(ctx.num_peers), 1)
        return P_ * self.wire_bytes_per_edge(grads_like, ctx)


# ---------------------------------------------------------------------------
# Byzantine-robust protocols (estimators in repro.core.robust)
# ---------------------------------------------------------------------------


class _RobustExchange(ExchangeProtocol):
    """Shared machinery of the robust family: gather the full dense bank,
    optionally norm-clip each peer row (``ctx.robust_clip``), and hand the
    bank to the subclass estimator.

    Wire accounting is HONEST about the robustness tax: these protocols
    need every neighbor's dense gradient materialized (order statistics /
    distance scores don't decompose into a fused reduction), so they
    inherit the dense ``allgather_mean`` byte counts — ``(P-1) x model``
    per peer on the full mesh, vs ``2(P-1)/P x model`` for ``psum_mean``
    and ``2(P-1)/P x model`` total for ``reduce_scatter``. That delta IS
    the robustness-vs-wire-cost trade-off fig12 quantifies.
    """

    def _mask(self, ctx: ExchangeContext):
        """(P,) closed-neighborhood mask for this rank, or None on the
        full graph (every peer is a member — skip the mask arithmetic)."""
        if ctx.mixing is None:
            return None
        closed = np.asarray(ctx.graph.adjacency) | np.eye(
            ctx.num_peers, dtype=bool
        )
        r = lax.axis_index(ctx.axis)
        return lax.dynamic_index_in_dim(
            jnp.asarray(closed), r, 0, keepdims=False
        )

    def _prepare(self, bank, ctx: ExchangeContext):
        if ctx.robust_clip > 0.0:
            return R.clip_bank_to_norm(bank, ctx.robust_clip)
        return bank

    def _aggregate(self, bank, mask, ctx: ExchangeContext):
        raise NotImplementedError

    def combine(self, grads, ctx, *, key=None, state=None):
        bank = jax.tree.map(
            lambda g: lax.all_gather(g.astype(ctx.wire_dtype), ctx.axis)
            .astype(jnp.float32),
            grads,
        )
        mask = self._mask(ctx)
        return self._aggregate(self._prepare(bank, ctx), mask, ctx), state

    def host_combine(self, grads_peers, rank: int, ctx: ExchangeContext):
        """Robust aggregate over the contributions that actually arrived
        (the mailbox already restricted consumption to graph edges, so
        the arrived set IS the closed neighborhood — possibly smaller
        under churn, which the order statistics absorb)."""
        ranks = sorted(grads_peers)
        bank = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]),
            *[grads_peers[j] for j in ranks],
        )
        return self._aggregate(self._prepare(bank, ctx), None, ctx)


@register_exchange("trimmed_mean")
class TrimmedMeanExchange(_RobustExchange):
    """Coordinate-wise trimmed mean: drop the ``f`` fraction of values
    from each end of every coordinate, mean the rest. ``trimmed_mean:f``
    (e.g. ``trimmed_mean:0.25``) sets the trim; bare ``trimmed_mean``
    reads ``ctx.trim_frac``. Survives up to ``f`` Byzantine peers per
    coordinate; ``f=0`` is exactly the plain mean (the equivalence rail).
    Composes with sparse overlays: each peer trims over its closed
    neighborhood instead of mixing with MH weights."""

    def __init__(self, param: Optional[str] = None):
        self.frac: Optional[float] = None
        if param is not None:
            self.frac = float(param)
            if not 0.0 <= self.frac < 0.5:
                raise ValueError(
                    f"trimmed_mean trim fraction must be in [0, 0.5), "
                    f"got {self.frac}"
                )

    def _trim(self, ctx) -> float:
        return ctx.trim_frac if self.frac is None else self.frac

    def _aggregate(self, bank, mask, ctx):
        frac = self._trim(ctx)

        def leaf(b):
            # host path under churn: bank rows = contributions that
            # ARRIVED, possibly < num_peers — size the mask from the leaf
            m = jnp.ones((b.shape[0],), bool) if mask is None else mask
            return R.masked_trimmed_mean(b, m, frac)

        return jax.tree.map(leaf, bank)


@register_exchange("median")
class CoordinateMedianExchange(_RobustExchange):
    """Coordinate-wise median — the no-hyperparameter robust baseline
    with breakdown point 1/2 per coordinate. Composes with sparse
    overlays (median over the closed neighborhood)."""

    def _aggregate(self, bank, mask, ctx):
        def leaf(b):
            m = jnp.ones((b.shape[0],), bool) if mask is None else mask
            return R.masked_median(b, m)

        return jax.tree.map(leaf, bank)


@register_exchange("krum")
class KrumExchange(_RobustExchange):
    """Krum / multi-Krum (Blanchard et al., 2017): score every
    contribution by its summed squared distance to its ``P - f - 2``
    nearest peers, average the ``m`` lowest-scored gradients.
    ``krum`` selects 1 (classic Krum); ``krum:m`` averages the top m.
    The pairwise distances need ALL contributions, so sparse overlays
    are refused (``requires_full_graph``), like ``reduce_scatter``."""

    requires_full_graph = True

    def __init__(self, param: Optional[str] = None):
        self.m: Optional[int] = None
        if param is not None:
            self.m = int(param)
            if self.m < 1:
                raise ValueError(f"krum selection count must be >= 1, got {self.m}")

    def _select_count(self, ctx) -> int:
        return ctx.krum_m if self.m is None else self.m

    def _aggregate(self, bank, mask, ctx):
        flat, unflatten = R.flatten_bank(bank)
        m = min(self._select_count(ctx), int(flat.shape[0]))
        agg, _ = R.krum_select(flat, m=m, f=ctx.krum_f)
        return unflatten(agg)
