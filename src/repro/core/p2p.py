"""P2P distributed training — Algorithm 1 of the paper, on a TPU mesh.

Peers are slices of the *manual* mesh axes (``peer_axes``); the serverless
lambda pool / tensor parallelism is the remaining *auto* axis handled by
GSPMD. The whole train step runs inside ``shard_map`` manual over
``peer_axes`` so the per-peer gradient ``g_{t,r}`` is a first-class value
and the gradient exchange is an explicit, swappable
:class:`~repro.core.exchange.ExchangeProtocol` resolved from the registry
by name:

  ``allgather_mean``  (paper-faithful)   publish/consume/average; the
                      all_gather IS the synchronization barrier (§III-B.6)
  ``psum_mean``       (beyond-paper)     one fused all-reduce, same math
  ``qsgd``            (paper §III-B.4)   int8 levels + bucket norms
  ``topk``            (beyond-paper)     top-k sparsified values + indices
  ``async``           (paper §III-B.5)   staleness-K mailbox register bank

``Topology(exchange="<name>")`` accepts any registered name, so adding a
protocol never touches this module. The overlay topology is equally
pluggable: ``Topology(graph="ring" | "gossip:3" | "hierarchical" | ...)``
resolves a :class:`~repro.core.graph.PeerGraph` whose Metropolis–Hastings
mixing matrix generalizes the sync protocols' global mean to
neighbor-weighted mixing (the full graph keeps the legacy bit-exact mean).
The train state is the :class:`TrainState` dataclass pytree (dict-style
access kept for backward compatibility).
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import compression as C
from repro.core import robust as R
from repro.core.exchange import (
    ExchangeContext,
    ExchangeProtocol,
    get_exchange,
)
from repro.core.graph import PeerGraph, get_graph
from repro.optim import Optimizer, apply_updates, clip_by_global_norm


@dataclass(frozen=True)
class Topology:
    """How the P2P system maps onto the mesh."""

    peer_axes: Tuple[str, ...] = ("data",)  # manual axes: one peer per slice
    lambda_axis: Optional[str] = "model"  # auto axis: serverless pool / TP
    exchange: str = "allgather_mean"  # any name in exchange.available_exchanges()
    graph: Any = "full"  # peer overlay: name in graph.available_graphs()
    #   ("ring", "gossip:3", ...) or a PeerGraph instance
    graph_seed: int = 0  # seeds stochastic overlays (gossip)
    qsgd: Optional[C.QSGDConfig] = None
    async_mode: bool = False  # DEPRECATED: use exchange="async"
    staleness: int = 1  # async: consume banks published K steps ago
    topk_frac: float = 0.01  # topk: fraction of entries shipped
    topk_impl: str = "jnp"  # topk select/scatter: "jnp" oracle | Pallas "kernel"
    # Error feedback (EF-SGD): accumulate the compression residual
    # r <- (g + r) - decode(encode(g + r)) per peer and re-inject it next
    # step. Keeps the biased top-k sparsifier convergent at aggressive
    # fractions; unbiased qsgd converges without it. No-op (residual
    # identically zero) for lossless protocols.
    ef: bool = False
    # robust-aggregation knobs (see repro.core.robust); a parameterized
    # spec (exchange="trimmed_mean:0.25" / "krum:3") overrides these
    trim_frac: float = 0.0  # trimmed_mean: fraction dropped from EACH end
    krum_m: int = 1  # krum: multi-Krum selection count
    krum_f: Optional[int] = None  # krum: assumed attackers (None = max)
    robust_clip: float = 0.0  # >0: per-peer norm clip before robust combine
    serverless: bool = True  # fan micro-batches out over lambda_axis
    grad_clip: float = 0.0
    # beyond-paper knobs (EXPERIMENTS.md §Perf):
    exchange_dtype: str = "float32"  # bfloat16 halves exchange wire bytes
    cast_params_once: bool = False  # one bf16 cast per step -> bf16 ZeRO gathers
    # Gradient accumulation: when a peer's m batches exceed the lambda
    # slots (the paper's Step-Functions queueing case), split the peer
    # batch into `accum_steps` sequential micro-rounds and average —
    # AverageBatchesGradients with bounded activation memory.
    accum_steps: int = 1

    def __post_init__(self):
        if self.async_mode:
            warnings.warn(
                'Topology(async_mode=True) is deprecated; use '
                'Topology(exchange="async") — one name per protocol',
                DeprecationWarning,
                stacklevel=3,
            )

    @property
    def axis(self):
        return self.peer_axes if len(self.peer_axes) > 1 else self.peer_axes[0]

    @property
    def exchange_name(self) -> str:
        return "async" if self.async_mode else self.exchange

    def protocol(self) -> ExchangeProtocol:
        return get_exchange(self.exchange_name)

    def peer_graph(self, num_peers: int) -> PeerGraph:
        """Resolve the overlay for ``num_peers`` ranks via the registry."""
        return get_graph(self.graph, num_peers, seed=self.graph_seed)


def peer_rank(topo: Topology) -> jnp.ndarray:
    return lax.axis_index(topo.axis)


def peer_count_static(topo: Topology, mesh) -> int:
    n = 1
    for a in topo.peer_axes:
        n *= mesh.shape[a]
    return n


def exchange_context(
    topo: Topology, mesh=None, *, num_peers: Optional[int] = None
) -> ExchangeContext:
    """Build the :class:`ExchangeContext` a protocol sees for ``topo``.

    Resolves the overlay graph for the peer count and attaches its
    Metropolis–Hastings mixing matrix; on the full graph (where MH is
    exactly uniform ``1/P``) ``mixing`` stays ``None`` so protocols keep
    the legacy bit-exact global-mean arithmetic.
    """
    if num_peers is None:
        num_peers = peer_count_static(topo, mesh) if (mesh is not None and topo.peer_axes) else 1
    graph = topo.peer_graph(num_peers)
    mixing = (
        None
        if (graph.is_full or num_peers <= 1)
        else graph.mixing_matrix().astype(np.float32)
    )
    proto = topo.protocol()
    if mixing is not None and (
        not proto.decomposes_per_edge or proto.requires_full_graph
    ):
        # fail at construction, not inside the first jitted step trace
        kind = (
            "a sharded global reduce-scatter"
            if proto.requires_full_graph and proto.decomposes_per_edge
            else "a fused global collective"
        )
        raise ValueError(
            f"exchange protocol {topo.exchange_name!r} is {kind} "
            f"and only supports graph='full'; got "
            f"{graph.describe()}"
        )
    return ExchangeContext(
        axis=topo.axis if topo.peer_axes else None,
        num_peers=num_peers,
        wire_dtype=jnp.dtype(topo.exchange_dtype),
        qsgd=topo.qsgd,
        topk_frac=topo.topk_frac,
        topk_impl=topo.topk_impl,
        staleness=topo.staleness,
        graph=graph,
        mixing=mixing,
        trim_frac=topo.trim_frac,
        krum_m=topo.krum_m,
        krum_f=topo.krum_f,
        robust_clip=topo.robust_clip,
    )


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------


@dataclass
class TrainState:
    """The train-step carry, as a registered dataclass pytree.

    Replaces the raw ``{"params": ..., "opt_state": ...}`` dict;
    ``state["params"]``, ``state.get("mailbox")`` and ``dict(state)`` keep
    working so existing call sites migrate incrementally. ``mailbox`` holds
    the exchange protocol's carried state (None for sync protocols);
    ``ef`` holds the per-peer error-feedback residual bank — leaves shaped
    ``(P, *param)`` — when ``Topology(ef=True)``, else None.
    """

    params: Any
    opt_state: Any
    step: Any
    key: Any
    mailbox: Any = None
    ef: Any = None

    # dict-style access (legacy call sites). Matches the old dict's
    # semantics: the optional fields ("mailbox", "ef") are only present
    # when set, so lookups of an absent one raise KeyError and membership
    # tests return False.
    def __getitem__(self, name: str):
        if name not in self.keys():
            raise KeyError(name)
        return getattr(self, name)

    def get(self, name: str, default=None):
        if name not in _TRAIN_STATE_FIELDS:
            return default
        val = getattr(self, name)
        return default if (name in _OPTIONAL_STATE_FIELDS and val is None) else val

    def keys(self):
        return [
            f for f in _TRAIN_STATE_FIELDS
            if not (f in _OPTIONAL_STATE_FIELDS and getattr(self, f) is None)
        ]

    def __contains__(self, name) -> bool:
        return name in self.keys()

    def __iter__(self):
        return iter(self.keys())

    def replace(self, **updates) -> "TrainState":
        return dataclasses.replace(self, **updates)


_TRAIN_STATE_FIELDS = tuple(f.name for f in dataclasses.fields(TrainState))
_OPTIONAL_STATE_FIELDS = ("mailbox", "ef")


def _train_state_flatten_with_keys(s: TrainState):
    children = tuple(
        (jax.tree_util.GetAttrKey(name), getattr(s, name))
        for name in _TRAIN_STATE_FIELDS
    )
    return children, None


def _train_state_flatten(s: TrainState):
    return tuple(getattr(s, name) for name in _TRAIN_STATE_FIELDS), None


def _train_state_unflatten(_, children) -> TrainState:
    return TrainState(*children)


jax.tree_util.register_pytree_with_keys(
    TrainState,
    _train_state_flatten_with_keys,
    _train_state_unflatten,
    _train_state_flatten,
)


def as_train_state(state) -> TrainState:
    """Accept a TrainState or a legacy state dict."""
    if isinstance(state, TrainState):
        return state
    if isinstance(state, Mapping):
        extra = set(state) - set(_TRAIN_STATE_FIELDS)
        if extra:
            # Refuse rather than silently dropping caller-carried entries.
            raise ValueError(
                f"legacy train-state dict has entries TrainState cannot carry: "
                f"{sorted(extra)}; TrainState fields are {_TRAIN_STATE_FIELDS}"
            )
        return TrainState(
            params=state["params"],
            opt_state=state["opt_state"],
            step=state["step"],
            key=state["key"],
            mailbox=state.get("mailbox"),
            ef=state.get("ef"),
        )
    raise TypeError(f"expected TrainState or mapping, got {type(state)!r}")


# ---------------------------------------------------------------------------
# Gradient exchange (registry-dispatched; see repro/core/exchange.py)
# ---------------------------------------------------------------------------


def exchange_gradients(
    grads,
    topo: Topology,
    key: Optional[jax.Array] = None,
    mailbox=None,
    *,
    num_peers: Optional[int] = None,
):
    """Returns (averaged_grads, new_mailbox) via the registered protocol.

    Thin compatibility wrapper over ``topo.protocol().combine``; the train
    step builder calls the protocol directly. ``num_peers`` must be passed
    explicitly for sync protocols (there is no mailbox state to infer it
    from); for async state the ring's axis-1 extent is accepted as a
    fallback but an explicit count always wins.
    """
    if not topo.peer_axes:
        return grads, mailbox
    inferred = _mailbox_peers(mailbox)
    if num_peers is None:
        num_peers = inferred
        if num_peers is None:
            raise ValueError(
                "exchange_gradients needs num_peers=...: it cannot be "
                "inferred without an async mailbox state (and graph-local "
                "state need not span all peers)"
            )
    elif inferred is not None and inferred != num_peers:
        raise ValueError(
            f"exchange_gradients got num_peers={num_peers} but the async "
            f"mailbox state spans {inferred} peers; the mixing weights "
            f"would silently mis-align — rebuild the mailbox for "
            f"{num_peers} peers or pass the matching count"
        )
    # exchange_context -> ExchangeContext.__post_init__ validates that the
    # resolved overlay graph matches num_peers, raising a clear error
    # instead of silently mis-mixing.
    ctx = exchange_context(topo, num_peers=num_peers)
    return topo.protocol().combine(grads, ctx, key=key, state=mailbox)


def _mailbox_peers(mailbox) -> Optional[int]:
    """Peer count from an async mailbox ring (leaves (K, P, *grad)), else None."""
    if mailbox is None:
        return None
    leaves = jax.tree.leaves(mailbox)
    return int(leaves[0].shape[1]) if leaves else None


def init_mailbox(grads_like, num_peers: int, *, staleness: int = 1):
    """Zero-initialized staleness-K mailbox ring, leaves (K, P, *grad)."""
    return get_exchange("async").init_state(
        grads_like, ExchangeContext(num_peers=num_peers, staleness=staleness)
    )


def init_ef(grads_like, num_peers: int):
    """Zero-initialized EF-SGD residual bank: leaves (P, *grad) fp32.

    The bank is replicated across the mesh (each peer reads/writes its own
    row inside the manual region and the rows are re-gathered so the carry
    stays consistent everywhere), mirroring the async mailbox layout.
    """
    return jax.tree.map(
        lambda g: jnp.zeros((num_peers,) + tuple(g.shape), jnp.float32),
        grads_like,
    )


# ---------------------------------------------------------------------------
# Serverless intra-peer fan-out (paper §III-C)
# ---------------------------------------------------------------------------


def lambda_shard(batch: Dict[str, jnp.ndarray], topo: Topology):
    """Fan the peer's micro-batches out over the lambda (auto) axis.

    Inside the manual region the leading dim of every batch leaf is the
    peer-local batch; constraining it over the lambda axis makes XLA compute
    per-lambda partial gradients and reduce them — the TPU-native image of
    the paper's parallel Lambda invocations + gradient averaging.
    """
    if not (topo.serverless and topo.lambda_axis):
        return batch
    ax = topo.lambda_axis
    return jax.tree.map(
        lambda x: lax.with_sharding_constraint(x, P(*((ax,) + (None,) * (x.ndim - 1)))),
        batch,
    )


# ---------------------------------------------------------------------------
# The P2P train step builder
# ---------------------------------------------------------------------------


def build_p2p_train_step(
    loss_fn: Callable,  # (params, batch) -> (loss, aux)
    optimizer: Optimizer,
    topo: Topology,
    mesh,
    schedule: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    adversary: Optional[R.AdversarySpec] = None,
):
    """Returns step(train_state, batch) -> (train_state, metrics).

    ``train_state`` is a :class:`TrainState` (legacy dicts still accepted).
    One code path serves both the peer (``shard_map`` over ``peer_axes``)
    and the no-peer (single worker) case: the peer body is identical, only
    the wrapping differs.

    ``adversary`` (a :class:`repro.core.robust.AdversarySpec`) makes the
    seeded attacker ranks publish poisoned gradients: their bank row is
    replaced (sign-flip / scaled noise) *before* the exchange collective,
    so every consumer — and the exchange protocol's estimator — sees the
    poisoned contribution. ``stale_replay`` is payload-level and only
    exists on the host mailbox path; it is refused here at build time.
    """
    protocol = topo.protocol() if topo.peer_axes else None
    ctx = exchange_context(topo, mesh) if topo.peer_axes else None
    attack_mask = None
    if adversary is not None and adversary.active and topo.peer_axes:
        if adversary.attack == "stale_replay":
            raise ValueError(
                "stale_replay replays a previous epoch's wire payload and "
                "only exists on the host mailbox path (LocalP2PCluster); "
                "use sign_flip or scaled_noise on the device path"
            )
        attack_mask = jnp.asarray(adversary.mask(ctx.num_peers))

    def exchange_scope():
        """``p2p.exchange`` around a protocol that opens no scopes of its own."""
        if protocol.scoped:
            return contextlib.nullcontext()
        return jax.named_scope("p2p.exchange")

    def fan_out(params, step_idx, key, batch):
        """The peer's loss and gradient over its batch (micro-batches
        accumulated in fp32), clipped and, on attacker ranks, poisoned."""
        batch = lambda_shard(batch, topo)
        if topo.cast_params_once:
            # One bf16 cast per step: ZeRO weight gathers then move bf16
            # instead of fp32 (halves per-layer gather bytes). Master params
            # and the optimizer stay fp32; norm vectors keep full precision.
            compute_params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if (p.dtype == jnp.float32 and p.ndim >= 2)
                else p,
                params,
            )
        else:
            compute_params = params
        if topo.accum_steps > 1:
            # sequential micro-rounds over the leading batch dim (each round
            # still fans out over the lambda axis); grads averaged in fp32
            n = topo.accum_steps

            def split(x):
                return x.reshape((n, x.shape[0] // n) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def round_fn(carry, mb):
                (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    compute_params, mb
                )
                acc_g, acc_l, acc_a = carry
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / n, acc_g, g
                )
                return (acc_g, acc_l + loss / n, acc_a + aux / n), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), compute_params
            )
            (grads, loss, aux), _ = lax.scan(
                round_fn, (zeros, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                micro,
            )
        else:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                compute_params, batch
            )
        if topo.grad_clip:
            grads, gnorm = clip_by_global_norm(grads, topo.grad_clip)
        else:
            gnorm = jnp.zeros((), jnp.float32)
        step_key = jax.random.fold_in(key, step_idx)
        if attack_mask is not None:
            # Byzantine ranks publish a poisoned contribution: the honest
            # gradient still exists locally, only the exchanged row flips.
            r = lax.axis_index(topo.axis)
            poison_key = jax.random.fold_in(jax.random.fold_in(step_key, 7919), r)
            poisoned = R.poison_gradients(grads, adversary, poison_key)
            grads = jax.tree.map(
                lambda h, p: jnp.where(attack_mask[r], p, h), grads, poisoned
            )
        return grads, loss, aux, gnorm, step_key

    def peer_body(params, opt_state, step_idx, key, batch, mailbox, ef):
        # One named scope per layer of the round (p2p.fanout, p2p.ef,
        # p2p.exchange and the codec's p2p.encode / p2p.gather / p2p.decode,
        # p2p.optimizer). They never nest, and each lands in the op_name of
        # every HLO instruction it holds, so a device trace can be split by
        # layer; they cost nothing at run time.
        with jax.named_scope("p2p.fanout"):
            grads, loss, aux, gnorm, step_key = fan_out(params, step_idx, key, batch)
        if protocol is None:
            avg, new_mailbox, new_ef = grads, mailbox, ef
        elif ef is not None:
            # EF-SGD: re-inject this peer's accumulated compression residual
            # before encoding, then keep what the codec dropped. local_image
            # is the decoded image of our shipped payload, so the residual
            # is exactly the information the swarm never received.
            with jax.named_scope("p2p.ef"):
                r = lax.axis_index(topo.axis)
                corrected = jax.tree.map(
                    lambda g, e: g.astype(jnp.float32) + e[r], grads, ef
                )
            with exchange_scope():
                avg, local_image, new_mailbox = protocol.combine_ef(
                    corrected, ctx, key=step_key, state=mailbox
                )
            with jax.named_scope("p2p.ef"):
                residual = jax.tree.map(
                    lambda c, l: c - l.astype(jnp.float32), corrected, local_image
                )
                # Re-gather the per-peer rows so the replicated carry stays
                # identical on every mesh slice (same layout as the async ring).
                new_ef = jax.tree.map(
                    lambda x: lax.all_gather(x, topo.axis), residual
                )
        else:
            with exchange_scope():
                avg, new_mailbox = protocol.combine(
                    grads, ctx, key=step_key, state=mailbox
                )
            new_ef = None
        with jax.named_scope("p2p.optimizer"):
            lr = schedule(step_idx)
            updates, opt_state = optimizer.update(avg, opt_state, params, lr)
            params = apply_updates(params, updates)
        if topo.peer_axes:
            with jax.named_scope("p2p.exchange"):
                loss = lax.pmean(loss, topo.axis)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, "aux": aux}
        return params, opt_state, metrics, new_mailbox, new_ef

    def run_body(state: TrainState, batch):
        if not topo.peer_axes:
            return peer_body(
                state.params, state.opt_state, state.step, state.key,
                batch, state.mailbox, state.ef,
            )
        replicated = P()
        bspec = jax.tree.map(lambda _: P(topo.axis), batch)
        mspec = (
            None if state.mailbox is None
            else jax.tree.map(lambda _: replicated, state.mailbox)
        )
        efspec = (
            None if state.ef is None
            else jax.tree.map(lambda _: replicated, state.ef)
        )
        fn = jax.shard_map(
            peer_body,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: replicated, state.params),
                jax.tree.map(lambda _: replicated, state.opt_state),
                replicated,
                replicated,
                bspec,
                mspec,
                efspec,
            ),
            out_specs=(
                jax.tree.map(lambda _: replicated, state.params),
                jax.tree.map(lambda _: replicated, state.opt_state),
                {"loss": replicated, "grad_norm": replicated, "lr": replicated,
                 "aux": replicated},
                mspec,
                efspec,
            ),
            axis_names=set(topo.peer_axes),
            check_vma=False,
        )
        return fn(
            state.params, state.opt_state, state.step, state.key,
            batch, state.mailbox, state.ef,
        )

    def step(state, batch):
        state = as_train_state(state)
        params, opt_state, metrics, mb, ef = run_body(state, batch)
        new_state = state.replace(
            params=params, opt_state=opt_state, step=state.step + 1,
            mailbox=mb, ef=ef,
        )
        return new_state, metrics

    return step
