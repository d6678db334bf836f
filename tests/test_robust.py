"""Byzantine-robust aggregation: combinators vs numpy references, the
adversary model on the host cluster (poisoned publishes, stale replay,
nonfinite rejection), robust-protocol equivalence rails, and the
ConvergenceDetector NaN regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import robust as R
from repro.core.convergence import (
    ConvergenceDetector,
    EarlyStopping,
    ReduceLROnPlateau,
)
from repro.core.exchange import ExchangeContext, get_exchange


# ---------------------------------------------------------------------------
# combinators vs numpy references
# ---------------------------------------------------------------------------


def test_masked_trimmed_mean_matches_numpy(rng):
    x = jnp.asarray(rng.normal(size=(7, 5, 3)), jnp.float32)
    full = jnp.ones((7,), bool)
    # f=0: plain mean
    np.testing.assert_allclose(
        np.asarray(R.masked_trimmed_mean(x, full, 0.0)),
        np.asarray(x).mean(0), rtol=1e-6,
    )
    # f=0.2: floor(0.2*7)=1 trimmed from each end, mean of middle 5
    got = np.asarray(R.masked_trimmed_mean(x, full, 0.2))
    ref = np.sort(np.asarray(x), axis=0)[1:-1].mean(0)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_masked_trimmed_mean_sparse_mask(rng):
    x = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    mask = jnp.asarray([True, False, True, True, False, True])
    sub = np.asarray(x)[np.asarray(mask)]
    # k=4 members, floor(0.25*4)=1 from each end
    ref = np.sort(sub, axis=0)[1:-1].mean(0)
    np.testing.assert_allclose(
        np.asarray(R.masked_trimmed_mean(x, mask, 0.25)), ref, rtol=1e-5
    )


def test_trim_clamped_below_half():
    x = jnp.asarray([[0.0], [1.0], [2.0]])
    m = jnp.ones((3,), bool)
    # f=0.45 of k=3 -> floor=1, clamped to (k-1)//2=1: median survives
    np.testing.assert_allclose(
        np.asarray(R.masked_trimmed_mean(x, m, 0.45)), [1.0]
    )
    with pytest.raises(ValueError):
        R.masked_trimmed_mean(x, m, 0.5)


def test_masked_median_matches_numpy(rng):
    for k in (3, 4, 7, 8):  # odd and even member counts
        x = jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)
        got = np.asarray(R.masked_median(x, jnp.ones((k,), bool)))
        np.testing.assert_allclose(got, np.median(np.asarray(x), 0), rtol=1e-5)
    x = jnp.asarray(rng.normal(size=(5, 2)), jnp.float32)
    mask = jnp.asarray([True, True, False, True, False])
    ref = np.median(np.asarray(x)[np.asarray(mask)], 0)
    np.testing.assert_allclose(
        np.asarray(R.masked_median(x, mask)), ref, rtol=1e-5
    )


def test_trimmed_mean_resists_planted_outlier(rng):
    honest = rng.normal(size=(6, 8)).astype(np.float32)
    bank = np.concatenate([honest, 1e6 * np.ones((2, 8), np.float32)])
    m = jnp.ones((8,), bool)
    tm = np.asarray(R.masked_trimmed_mean(jnp.asarray(bank), m, 0.25))
    md = np.asarray(R.masked_median(jnp.asarray(bank), m))
    honest_mean = honest.mean(0)
    # order statistics of 6 N(0,1) samples deviate O(1) from their mean;
    # what matters is the outliers' 1e6 never leaks in
    assert np.abs(tm - honest_mean).max() < 2.5
    assert np.abs(md - honest_mean).max() < 2.5
    # the plain mean is destroyed by the same bank
    assert np.abs(bank.mean(0) - honest_mean).max() > 1e5


def test_krum_scores_and_select(rng):
    flat = jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
    f = 1
    scores = np.asarray(R.krum_scores(flat, f=f))
    x = np.asarray(flat)
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    ref = np.sort(d2, 1)[:, : 6 - f - 2].sum(1)
    np.testing.assert_allclose(scores, ref, rtol=1e-4)
    agg, sel = R.krum_select(flat, m=1, f=f)
    assert int(sel[0]) == int(np.argmin(ref))
    np.testing.assert_allclose(np.asarray(agg), x[int(np.argmin(ref))],
                               rtol=1e-6)
    # multi-Krum: mean of the m lowest-scored rows
    agg2, sel2 = R.krum_select(flat, m=3, f=f)
    np.testing.assert_allclose(
        np.asarray(agg2), x[np.argsort(ref)[:3]].mean(0), rtol=1e-5
    )


def test_krum_excludes_far_attacker(rng):
    honest = rng.normal(size=(5, 16)).astype(np.float32)
    attacker = 100.0 + rng.normal(size=(1, 16)).astype(np.float32)
    flat = jnp.asarray(np.concatenate([honest, attacker]))
    _, sel = R.krum_select(flat, m=1, f=1)
    assert int(sel[0]) != 5  # never the far-away row


def test_krum_validation():
    flat = jnp.zeros((2, 4))
    with pytest.raises(ValueError):
        R.krum_scores(flat)  # P >= 3
    with pytest.raises(ValueError):
        R.krum_scores(jnp.zeros((4, 3)), f=2)  # f <= P - 3


def test_bank_norm_clipping(rng):
    bank = {"w": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)}
    norms = np.asarray(R.bank_peer_norms(bank))
    ref = np.linalg.norm(np.asarray(bank["w"]), axis=1)
    np.testing.assert_allclose(norms, ref, rtol=1e-5)
    clipped = R.clip_bank_to_norm(bank, 0.5)
    cn = np.asarray(R.bank_peer_norms(clipped))
    assert (cn <= 0.5 + 1e-5).all()


# ---------------------------------------------------------------------------
# AdversarySpec
# ---------------------------------------------------------------------------


def test_adversary_spec_seeded_and_fraction():
    a = R.AdversarySpec(fraction=0.25, seed=3)
    assert a.num_attackers(8) == 2
    assert a.attackers(8) == a.attackers(8)  # deterministic in the seed
    b = R.AdversarySpec(fraction=0.25, seed=4)
    assert set(a.attackers(100)) != set(b.attackers(100))
    m = a.mask(8)
    assert m.dtype == bool and m.sum() == 2
    assert all(a.is_attacker(r, 8) == bool(m[r]) for r in range(8))
    assert R.AdversarySpec(num=3).num_attackers(8) == 3
    assert not R.AdversarySpec().active
    assert "sign_flip" in R.AdversarySpec(fraction=0.5).describe()


def test_adversary_spec_validation():
    with pytest.raises(ValueError):
        R.AdversarySpec(fraction=1.5)
    with pytest.raises(ValueError):
        R.AdversarySpec(attack="meteor")
    with pytest.raises(ValueError):
        R.AdversarySpec(num=-1)


def test_poison_gradients_kinds():
    g = {"w": jnp.ones((3,)), "b": -2.0 * jnp.ones((2,))}
    spec = R.AdversarySpec(fraction=0.5, attack="sign_flip", scale=10.0)
    p = R.poison_gradients(g, spec, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(p["w"]), -10.0 * np.ones(3))
    np.testing.assert_allclose(np.asarray(p["b"]), 20.0 * np.ones(2))
    noisy = R.poison_gradients(
        g, R.AdversarySpec(fraction=0.5, attack="scaled_noise", scale=5.0),
        jax.random.PRNGKey(0),
    )
    assert float(jnp.abs(noisy["w"]).max()) > 0  # noise, not the honest g
    with pytest.raises(ValueError, match="stale_replay"):
        R.poison_gradients(
            g, R.AdversarySpec(fraction=0.5, attack="stale_replay"),
            jax.random.PRNGKey(0),
        )


def test_tree_all_finite():
    assert R.tree_all_finite({"a": jnp.ones(3), "b": jnp.zeros(2)})
    assert not R.tree_all_finite({"a": jnp.asarray([1.0, float("nan")])})
    assert not R.tree_all_finite({"a": jnp.asarray([float("inf")])})


# ---------------------------------------------------------------------------
# host cluster: adversary + robust protocols end to end
# ---------------------------------------------------------------------------


def _cluster(**kw):
    from repro.configs import get_config
    from repro.core import LocalP2PCluster
    from repro.data import make_dataset
    from repro.optim import sgd

    base = dict(
        num_peers=4, batch_size=8, batches_per_epoch=2,
        optimizer=sgd(momentum=0.9), lr=0.05, sync=True, seed=0,
    )
    base.update(kw)
    return LocalP2PCluster(
        get_config("squeezenet1.1"),
        make_dataset("mnist", size=128, image_hw=8, channels=1),
        **base,
    )


@pytest.mark.slow
def test_cluster_zero_trim_equivalent_to_mean():
    a = _cluster(exchange="allgather_mean")
    b = _cluster(exchange="trimmed_mean:0")
    a.run(2)
    b.run(2)
    err = max(
        float(jnp.abs(x - y).max())
        for x, y in zip(jax.tree.leaves(a.peers[0].params),
                        jax.tree.leaves(b.peers[0].params))
    )
    assert err <= 1e-6, err


@pytest.mark.slow
def test_cluster_adversary_poisons_wire_not_self():
    adv = R.AdversarySpec(num=1, attack="sign_flip", scale=10.0, seed=1)
    cl = _cluster(exchange="median", adversary=adv)
    cl.run_epoch_sync(0)
    (attacker,) = adv.attackers(4)
    assert cl.mailbox.stats["poisoned_publishes"] == 1
    # the attacker's register holds the poisoned payload, visible to all
    msg = cl.mailbox.consume(attacker)
    honest = (r for r in range(4) if r != attacker)
    assert msg is not None and msg.epoch == 0


@pytest.mark.slow
def test_cluster_stale_replay_ships_previous_epoch():
    adv = R.AdversarySpec(num=1, attack="stale_replay", seed=2)
    cl = _cluster(exchange="allgather_mean", adversary=adv)
    (attacker,) = adv.attackers(4)
    cl.run_epoch_sync(0)
    # epoch 0: no cached payload yet -> honest publish
    assert cl.mailbox.stats["poisoned_publishes"] == 0
    first = cl.mailbox.consume(attacker).payload
    cl.run_epoch_sync(1)
    # epoch 1: the wire carries epoch 0's payload verbatim
    assert cl.mailbox.stats["poisoned_publishes"] == 1
    replayed = cl.mailbox.consume(attacker).payload
    assert all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(first), jax.tree.leaves(replayed))
    )


@pytest.mark.slow
def test_cluster_rejects_nonfinite_contribution():
    cl = _cluster(exchange="allgather_mean", reject_nonfinite=True)
    grads = {p.rank: None for p in cl.peers}
    for peer in cl.peers:
        g, _, _, _ = cl._compute_peer_gradient(peer, 0)
        grads[peer.rank] = g
    # peer 3 publishes NaNs; everyone else publishes honestly
    bad = jax.tree.map(lambda x: x * jnp.nan, grads[3])
    for peer in cl.peers:
        cl._publish(peer, bad if peer.rank == 3 else grads[peer.rank],
                    0, at_time=0.0)
    gp, _ = cl._consume_all(cl.peers[0], grads[0], at_time=None)
    assert 3 not in gp  # dropped at the trust boundary
    assert set(gp) == {0, 1, 2}
    assert cl.mailbox.stats["rejected_nonfinite"] == 1


def test_cluster_refuses_adversary_on_sharded_protocol():
    with pytest.raises(ValueError, match="whole-gradient"):
        _cluster(exchange="reduce_scatter",
                 adversary=R.AdversarySpec(num=1))


def test_device_path_refuses_stale_replay():
    from repro.core.p2p import Topology, build_p2p_train_step
    from repro.optim import sgd as _sgd

    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="host mailbox"):
        build_p2p_train_step(
            lambda p, b: (jnp.float32(0), jnp.float32(0)),
            _sgd(), Topology(peer_axes=("data",)), mesh, lambda s: 0.1,
            adversary=R.AdversarySpec(num=1, attack="stale_replay"),
        )


def test_krum_exchange_refuses_sparse_graph():
    with pytest.raises(ValueError, match="full"):
        _cluster(exchange="krum", graph="ring")


def test_host_combine_fallback_is_none():
    # non-robust protocols keep the legacy mixing path
    proto = get_exchange("allgather_mean")
    assert proto.host_combine({0: {"w": jnp.ones(2)}}, 0,
                              ExchangeContext(num_peers=1)) is None


# ---------------------------------------------------------------------------
# satellite: ConvergenceDetector NaN handling
# ---------------------------------------------------------------------------


def test_plateau_nan_counts_as_bad_epoch():
    p = ReduceLROnPlateau(0.1, mode="min", patience=1)
    p.step(1.0)
    lr0 = p.lr
    p.step(float("nan"))
    p.step(float("nan"))  # patience exceeded -> reduce
    assert p.lr < lr0
    assert p.best == 1.0  # NaN never becomes "best"


def test_plateau_inf_never_improves_even_first():
    p = ReduceLROnPlateau(0.1, mode="max", patience=0)
    p.step(float("-inf"))
    assert p.best is None
    p.step(float("inf"))
    assert p.best is None  # +inf in max mode would be unbeatable
    p.step(0.5)
    assert p.best == 0.5


def test_early_stopping_nan_streak_stops():
    s = EarlyStopping(mode="min", patience=2)
    assert not s.step(1.0)
    assert not s.step(float("nan"))
    assert s.step(float("nan"))  # two bad epochs -> stop
    assert s.best == 1.0


def test_early_stopping_nan_first_metric_not_best():
    s = EarlyStopping(mode="min", patience=3)
    s.step(float("nan"))
    assert s.best is None
    s.step(2.0)
    assert s.best == 2.0


def test_convergence_detector_diverged_run_stops():
    det = ConvergenceDetector(0.1, mode="min", plateau_patience=1,
                              stop_patience=3, max_epochs=100)
    det.step(1.0)
    stopped = False
    for _ in range(4):
        stopped = det.step(float("nan"))
    assert stopped
    assert det.plateau.best == 1.0
