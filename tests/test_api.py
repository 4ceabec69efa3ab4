"""The public API (repro.api): registry parity, capability validation,
the shared BlockCSR cache, and the estimator.

Load-bearing properties:

1. **Registry parity** — for every registered method, ``solve(spec)`` is
   bit-identical (iterates, objective history, comm scalars, modeled
   time) to the direct driver call it wraps.  The front door adds
   dispatch, never numerics.
2. **Shim parity** — ``benchmarks.common.run_method`` (now a thin shim
   over ``solve``) reproduces the pre-redesign dispatcher bit-for-bit at
   the benchmark defaults, including the per-method ``"paper"`` rules
   (ETA table, trajectory mini-batch, ``m = N/u`` and its cap) that
   moved into the registry.
3. **Loud capability mismatches** — ``use_kernels`` on a driver without
   a kernel path, Option II on a driver that ignores it, a mesh on a
   non-shard_map method: all raise instead of silently running something
   other than what the caller asked for.
4. The bounded BlockCSR cache semantics (per-sweep scope + LRU), ported
   here from the benchmarks module along with the cache itself.
5. ``FDSVRGClassifier`` fit/partial_fit(warm start)/predict/score.
6. The carried snapshot (``repro.api.cache.SNAPSHOT_SLOT``): a call
   warm-started from the iterate the last call returned, on the same
   data, method, partition and loss, starts from that call's final
   snapshot and runs bit for bit as one that computes it; any other
   call computes it.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import (
    BLOCK_CACHE,
    BlockCache,
    ExperimentSpec,
    FDSVRGClassifier,
    METHODS,
    PAPER_MAX_INNER,
    as_padded_csr,
    method_info,
    solve,
)
from repro.api.cache import SNAPSHOT_SLOT
from repro.api.registry import _resolve
from repro.core import baselines, losses
from repro.core.driver import resolve_init_w
from repro.core.fdsvrg import (
    SVRGConfig,
    fdsvrg_worker_simulation,
    run_fdsvrg,
    run_serial_svrg,
)
from repro.core.fdsvrg_shardmap import FDSVRGShardedConfig, run_fdsvrg_sharded
from repro.core.partition import balanced
from repro.data.sparse import PaddedCSR
from repro.data.synthetic import make_sparse_classification
from repro.dist import make_mesh

LOSS = losses.logistic
REG = losses.l2(1e-3)


@pytest.fixture(scope="module")
def data():
    # n divisible by the q and u used below so the paper-M rules are exact.
    return make_sparse_classification(
        dim=512, num_instances=48, nnz_per_instance=8, seed=2
    )


def _assert_same_run(a, b):
    """Bit-identity across the full RunResult surface."""
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    assert [h.objective for h in a.history] == [h.objective for h in b.history]
    assert [h.grad_norm for h in a.history] == [h.grad_norm for h in b.history]
    assert a.meter.total_scalars == b.meter.total_scalars
    assert a.meter.total_rounds == b.meter.total_rounds
    assert [h.modeled_time_s for h in a.history] == [
        h.modeled_time_s for h in b.history
    ]


# ---------------------------------------------------------------------------
# 1. registry parity: solve(spec) == the direct driver call, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", sorted(METHODS))
def test_solve_matches_direct_driver(data, method):
    q = 1 if method == "fdsvrg_sharded" else 2
    eta, inner, u, outers = 0.3, 8, 2, 2
    cfg = SVRGConfig(eta=eta, inner_steps=inner, outer_iters=outers,
                     batch_size=u, seed=0)
    mesh = None
    if method == "serial":
        direct = run_serial_svrg(data, LOSS, REG, cfg)
    elif method == "fdsvrg":
        direct = run_fdsvrg(data, balanced(data.dim, q), LOSS, REG, cfg)
    elif method == "fdsvrg_sim":
        direct = fdsvrg_worker_simulation(
            data, balanced(data.dim, q), LOSS, REG, cfg
        )
    elif method == "fdsvrg_sharded":
        mesh = make_mesh((1,), ("model",))
        shcfg = FDSVRGShardedConfig(
            dim=data.dim, num_instances=data.num_instances,
            nnz_max=data.nnz_max, eta=eta, inner_steps=inner, batch_size=u,
            lam=REG.lam,
        )
        direct = run_fdsvrg_sharded(
            data, mesh, shcfg, feature_axes=("model",), outer_iters=outers,
            seed=0,
        )
    elif method in ("fd_saga", "fd_bcd"):
        from repro.data.block_csr import BlockCSR
        from repro.dist import SimBackend
        from repro.optim.update_rules import (
            BCDRule, SAGARule, make_context, run_with_rule,
        )

        rule = SAGARule() if method == "fd_saga" else BCDRule()
        direct = run_with_rule(rule, make_context(
            BlockCSR.from_padded(data, balanced(data.dim, q)),
            LOSS, REG, cfg, backend=SimBackend(q, None),
        ))
    else:
        runner = {
            "dsvrg": baselines.run_dsvrg,
            "synsvrg": baselines.run_syn_svrg,
            "asysvrg": baselines.run_asy_svrg,
            "pslite_sgd": baselines.run_pslite_sgd,
        }[method]
        direct = runner(data, q, LOSS, REG, cfg)

    via_api = solve(ExperimentSpec(
        method=method, data=data, reg=REG,
        q=None if method == "fdsvrg_sharded" else q,
        eta=eta, batch_size=u, inner_steps=inner, outer_iters=outers,
        mesh=mesh,
    ))
    _assert_same_run(via_api, direct)


# ---------------------------------------------------------------------------
# 2. shim parity: run_method == the pre-redesign dispatcher, bit for bit
# ---------------------------------------------------------------------------


def _legacy_run_method(method, data, q, lam, *, reg=None, eta=None,
                       outer_iters=6, batch_size=None, seed=0,
                       use_kernels=False):
    """The dispatcher exactly as benchmarks/common.py shipped it before
    the registry existed (PR 4 state), minus the lam/reg mismatch error.
    The constants are intentionally inlined, NOT imported from the
    registry — this is the independent oracle the shim is pinned to."""
    from repro.data.block_csr import BlockCSR
    from benchmarks.common import CLUSTER

    ETA = {"fdsvrg": 2.0, "serial": 2.0, "dsvrg": 1.0,
           "synsvrg": 2.0, "asysvrg": 0.5, "pslite_sgd": 0.3}
    U_TRAJ, MAX_INNER = 8, 12_000

    if reg is None:
        reg = losses.l2(lam)
    n = data.num_instances
    eta = ETA[method] if eta is None else eta
    if method == "fdsvrg":
        u = U_TRAJ if batch_size is None else batch_size
        m = min(max(1, n // u), MAX_INNER)
        cfg = SVRGConfig(eta=eta, inner_steps=m,
                         outer_iters=outer_iters, batch_size=u, seed=seed)
        return run_fdsvrg(data, balanced(data.dim, q), LOSS, reg, cfg,
                          CLUSTER, use_kernels=use_kernels,
                          block_data=BlockCSR.from_padded(
                              data, balanced(data.dim, q)))
    if method == "serial":
        cfg = SVRGConfig(eta=eta, inner_steps=min(n, MAX_INNER),
                         outer_iters=outer_iters, seed=seed)
        return run_serial_svrg(data, LOSS, reg, cfg, use_kernels=use_kernels)
    if method in ("dsvrg", "synsvrg"):
        cfg = SVRGConfig(eta=eta, inner_steps=min(max(1, n // q), MAX_INNER),
                         outer_iters=outer_iters, seed=seed)
        runner = {"dsvrg": baselines.run_dsvrg,
                  "synsvrg": baselines.run_syn_svrg}[method]
        return runner(data, q, LOSS, reg, cfg, CLUSTER)
    cfg = SVRGConfig(eta=eta, inner_steps=min(n, MAX_INNER),
                     outer_iters=outer_iters, seed=seed)
    runner = {"asysvrg": baselines.run_asy_svrg,
              "pslite_sgd": baselines.run_pslite_sgd}[method]
    return runner(data, q, LOSS, reg, cfg, CLUSTER)


@pytest.mark.parametrize(
    "method", ["fdsvrg", "serial", "dsvrg", "synsvrg", "asysvrg", "pslite_sgd"]
)
def test_run_method_shim_matches_pre_redesign(data, method):
    from benchmarks.common import run_method

    legacy = _legacy_run_method(method, data, 4, 1e-3, outer_iters=2)
    shim = run_method(method, data, 4, 1e-3, outer_iters=2)
    _assert_same_run(shim, legacy)


def test_run_method_shim_honors_explicit_eta_and_batch(data):
    from benchmarks.common import run_method

    legacy = _legacy_run_method("fdsvrg", data, 2, 1e-3, eta=0.7,
                                batch_size=4, outer_iters=2)
    shim = run_method("fdsvrg", data, 2, 1e-3, eta=0.7, batch_size=4,
                      outer_iters=2)
    _assert_same_run(shim, legacy)


def test_run_method_shim_honors_batch_for_fd_family(data):
    """fdsvrg_sim is newly reachable through the shim; an explicit
    batch_size must reach it (not silently fall back to the paper u)."""
    from benchmarks.common import CLUSTER, run_method

    shim = run_method("fdsvrg_sim", data, 2, 1e-3, batch_size=4,
                      outer_iters=2)
    via_api = solve(ExperimentSpec(
        method="fdsvrg_sim", data=data, q=2, reg=losses.l2(1e-3),
        batch_size=4, outer_iters=2, cluster=CLUSTER,
    ))
    # if the shim dropped batch_size to the paper u, the sample stream
    # (and therefore the iterates) could not match the explicit-u spec
    _assert_same_run(shim, via_api)
    paper = run_method("fdsvrg_sim", data, 2, 1e-3, outer_iters=2)
    assert not np.array_equal(np.asarray(shim.w), np.asarray(paper.w))


def test_run_method_reg_override_no_mismatch_error(data):
    """The lam/reg dual-argument footgun is dead: an override regularizer
    IS the regularizer, the headline lambda derives from it, and a
    (previously fatal) disagreeing lam is simply not consulted."""
    from benchmarks.common import run_method

    reg = losses.l1(5e-4)
    res = run_method("fdsvrg", data, 2, 1e-3, reg=reg, outer_iters=2)
    legacy = _legacy_run_method("fdsvrg", data, 2, None, reg=reg,
                                outer_iters=2)
    _assert_same_run(res, legacy)


# ---------------------------------------------------------------------------
# 3. validation: capability mismatches fail loudly
# ---------------------------------------------------------------------------


def test_use_kernels_rejected_for_non_kernel_methods(data):
    for method in ("dsvrg", "synsvrg", "asysvrg", "pslite_sgd",
                   "fdsvrg_sharded"):
        assert not method_info(method).supports_kernels
        with pytest.raises(ValueError, match="use_kernels"):
            solve(ExperimentSpec(method=method, data=data, use_kernels=True))


def test_option_ii_rejected_where_ignored(data):
    for method in ("asysvrg", "pslite_sgd", "fdsvrg_sharded"):
        with pytest.raises(ValueError, match="Option I/II"):
            solve(ExperimentSpec(method=method, data=data, option="II"))


def test_mesh_rejected_for_non_shardmap_methods(data):
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="mesh"):
        solve(ExperimentSpec(method="serial", data=data, mesh=mesh))


def test_tree_mode_rejected_for_non_shardmap_methods(data):
    with pytest.raises(ValueError, match="tree_mode"):
        solve(ExperimentSpec(method="dsvrg", data=data,
                             tree_mode="butterfly"))


def test_mesh_q_mismatch_rejected(data):
    with pytest.raises(ValueError, match="mesh"):
        solve(ExperimentSpec(method="fdsvrg_sharded", data=data, q=8))


def test_unknown_method_lists_registry(data):
    with pytest.raises(ValueError, match="registered methods"):
        solve(ExperimentSpec(method="sgd", data=data))


def test_spec_structural_validation(data):
    with pytest.raises(ValueError, match="exactly one"):
        ExperimentSpec(method="serial")
    with pytest.raises(ValueError, match="exactly one"):
        ExperimentSpec(method="serial", dataset="news20", data=data)
    with pytest.raises(TypeError, match="ONE regularizer"):
        ExperimentSpec(method="serial", data=data, reg=1e-4)
    with pytest.raises(ValueError, match="option"):
        ExperimentSpec(method="serial", data=data, option="III")
    with pytest.raises(ValueError, match="eta"):
        ExperimentSpec(method="serial", data=data, eta="auto")
    with pytest.raises(ValueError, match="batch_size"):
        ExperimentSpec(method="serial", data=data, batch_size=0)
    with pytest.raises(ValueError, match="inner_steps"):
        ExperimentSpec(method="serial", data=data, inner_steps=0)
    with pytest.raises(ValueError, match="outer_iters"):
        ExperimentSpec(method="serial", data=data, outer_iters=0)
    with pytest.raises(ValueError, match="eta"):
        ExperimentSpec(method="serial", data=data, eta=0.0)


def test_paper_rules_resolve_per_method():
    """The m = N/u and m = N/q rules (and the inner cap) live in the
    registry, per method, exactly as the benchmarks ran them."""
    n = 100
    r = _resolve(ExperimentSpec(method="fdsvrg", dataset="news20"),
                 method_info("fdsvrg"), n, q=4)
    assert (r.eta, r.batch_size, r.inner_steps) == (2.0, 8, 100 // 8)
    r = _resolve(ExperimentSpec(method="serial", dataset="news20"),
                 method_info("serial"), n, q=4)
    assert (r.eta, r.batch_size, r.inner_steps) == (2.0, 1, 100)
    r = _resolve(ExperimentSpec(method="dsvrg", dataset="news20"),
                 method_info("dsvrg"), n, q=4)
    assert (r.eta, r.batch_size, r.inner_steps) == (1.0, 1, 25)
    r = _resolve(ExperimentSpec(method="pslite_sgd", dataset="news20"),
                 method_info("pslite_sgd"), 10**6, q=4)
    assert r.inner_steps == PAPER_MAX_INNER  # the cap


def test_capability_matrix_covers_every_method():
    from repro.api import capability_matrix

    rows = {r["method"] for r in capability_matrix()}
    assert rows == set(METHODS)


# ---------------------------------------------------------------------------
# 4. the shared BlockCSR cache (ported from the benchmarks module)
# ---------------------------------------------------------------------------


def test_block_cache_bounded_and_per_sweep():
    """A second data set evicts the first (per-sweep scope), and the
    entry count stays bounded even for many q values."""
    a = make_sparse_classification(dim=64, num_instances=8,
                                   nnz_per_instance=4, seed=0)
    b = make_sparse_classification(dim=64, num_instances=8,
                                   nnz_per_instance=4, seed=1)
    cache = BlockCache(max_entries=4)
    blk_a2 = cache.get(a, 2)
    assert cache.get(a, 2) is blk_a2  # hit
    cache.get(a, 4)
    assert len(cache) == 2
    cache.get(b, 2)
    # every surviving entry belongs to b: a's blocks were evicted
    assert all(obj is b for obj, _ in cache.values())
    # LRU bound holds for many q values of one data set
    for q in (1, 2, 4, 8, 16, 32):
        cache.get(b, q)
    assert len(cache) <= cache.max_entries


def test_solve_reuses_the_shared_cache(data):
    BLOCK_CACHE.clear()
    spec = ExperimentSpec(method="fdsvrg", data=data, reg=REG, eta=0.3,
                          batch_size=2, inner_steps=4, outer_iters=1, q=2)
    solve(spec)
    blk = BLOCK_CACHE.get(data, 2)  # hit: solve built it
    assert len(BLOCK_CACHE) == 1
    solve(spec)
    assert BLOCK_CACHE.get(data, 2) is blk  # still the same entry
    # the whole FD family goes through the cache, not just fdsvrg
    solve(spec.replace(method="fdsvrg_sim"))
    assert BLOCK_CACHE.get(data, 2) is blk
    assert len(BLOCK_CACHE) == 1
    BLOCK_CACHE.clear()


def test_dataset_name_specs_hit_the_cache_across_solves():
    """solve() memoizes datasets.load, so dataset-NAME sweeps (the
    to_spec()/CLI path) reuse one data object and the id()-keyed
    BlockCSR cache hits instead of being evicted every call."""
    from repro.api.registry import _load_dataset

    assert _load_dataset("news20") is _load_dataset("news20")
    BLOCK_CACHE.clear()
    spec = ExperimentSpec(method="fdsvrg", dataset="news20", reg=REG,
                          eta=0.5, batch_size=2, inner_steps=2,
                          outer_iters=1, q=2)
    solve(spec)
    blk = BLOCK_CACHE.get(_load_dataset("news20"), 2)
    solve(spec.replace(reg=losses.l1(1e-4)))
    assert BLOCK_CACHE.get(_load_dataset("news20"), 2) is blk
    BLOCK_CACHE.clear()


def test_estimator_partial_fit_reuses_encoded_data():
    """Warm-start calls on the same (X, y) reuse ONE encoded data set —
    the label re-encode must not mint a fresh PaddedCSR per call (that
    would evict the BlockCSR cache on every partial_fit)."""
    raw = make_sparse_classification(dim=64, num_instances=16,
                                     nnz_per_instance=4, seed=3)
    y01 = (np.asarray(raw.labels) > 0).astype(int)  # {0,1}: forces re-wrap
    clf = FDSVRGClassifier(method="fdsvrg", workers=2, eta=0.3, lam=1e-3,
                           batch_size=2, inner_steps=4, outer_iters=1)
    clf.fit(raw, y01)
    encoded = clf._encoded[2]
    assert set(np.unique(np.asarray(encoded.labels))) == {-1.0, 1.0}
    clf.partial_fit(raw, y01)
    assert clf._encoded[2] is encoded  # same object, cache stays warm
    # re-encoded labels follow the data's values dtype (no mixed precision)
    assert encoded.labels.dtype == raw.values.dtype


def test_as_padded_csr_dense_length_mismatch():
    with pytest.raises(ValueError, match="labels but X holds"):
        as_padded_csr(np.ones((3, 2)), np.array([1.0, -1.0]))


def test_estimator_score_decodes_stored_labels():
    """score(X) with y=None must agree with score(X, y) when the model
    was fitted on classes other than the PaddedCSR's ±1 coding."""
    raw = make_sparse_classification(dim=64, num_instances=16,
                                     nnz_per_instance=4, seed=5)
    y01 = (np.asarray(raw.labels) > 0).astype(int)
    clf = FDSVRGClassifier(method="serial", eta=0.3, lam=1e-3,
                           inner_steps=8, outer_iters=2)
    clf.fit(raw, y01)
    assert clf.score(raw) == clf.score(raw, y01)


def test_register_method_summary_fallbacks():
    """A third-party adapter with neither summary= nor a docstring must
    register cleanly (empty summary), not die on an IndexError."""
    from repro.api import METHODS, register_method

    @register_method("_tmp_nodoc", backend="sim", supports_kernels=False,
                     paper_eta=1.0, inner_rule="n")
    def _adapter(spec, data, p, mesh):
        return None

    try:
        assert METHODS["_tmp_nodoc"].summary == ""
    finally:
        del METHODS["_tmp_nodoc"]


def test_estimator_string_labels_dense_input():
    """Labels 'may be any two values' includes non-numeric ones on the
    dense path: encoding happens before the sparse conversion."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(20, 8))
    y = np.where(rng.random(20) < 0.5, "ham", "spam")
    y[:2] = ["ham", "spam"]  # both classes present
    clf = FDSVRGClassifier(method="serial", eta=0.5, lam=1e-3,
                           inner_steps=20, outer_iters=2)
    clf.fit(X, y)
    assert set(np.unique(clf.predict(X))) <= {"ham", "spam"}
    assert 0.0 <= clf.score(X, y) <= 1.0


# ---------------------------------------------------------------------------
# 5. warm start (init_w) through the harness
# ---------------------------------------------------------------------------


def test_init_w_resolves_and_validates(data):
    w = resolve_init_w(None, 8, jnp.float32)
    assert w.shape == (8,) and not w.any()
    w = resolve_init_w(np.ones(8, np.float64), 8, jnp.float32)
    assert w.dtype == jnp.float32  # no silent promotion of the run
    with pytest.raises(ValueError, match="init_w"):
        resolve_init_w(np.ones(4), 8, jnp.float32)


@pytest.mark.parametrize("method", ["serial", "fdsvrg", "dsvrg"])
def test_warm_start_continues_from_given_iterate(data, method):
    """The outer-0 snapshot is taken at init_w, so a warm-started run
    reports its first outer from the trained iterate, not from zeros."""
    base = ExperimentSpec(method=method, data=data, reg=REG, q=2, eta=0.3,
                          batch_size=2, inner_steps=8, outer_iters=2)
    cold = solve(base)
    warm = solve(base.replace(init_w=cold.w, seed=1, outer_iters=1))
    assert warm.history[0].objective < cold.history[0].objective


# ---------------------------------------------------------------------------
# 5b. the carried snapshot
# ---------------------------------------------------------------------------


def _counted(directory, fn):
    """``fn()`` under the profiler, and the repro.obs counters it added."""
    obs.reset()
    with jax.profiler.trace(str(directory)):
        out = fn()
    counters = obs.totals()["counters"]
    obs.reset()
    return out, counters


def _lanes(data, q):
    bd = BLOCK_CACHE.get(data, q)
    return sum(int(i.size) for g in bd.groups for i in g.indices)


@pytest.mark.parametrize("method,q", [("serial", None), ("fdsvrg", 2),
                                      ("fdsvrg", 4)])
def test_warm_call_from_returned_iterate_equals_one_that_misses_the_slot(
        tmp_path, data, method, q):
    """From ``jax.block_until_ready(res.w)``, as the benchmark calls, the
    next call carries the snapshot and computes one full gradient an
    outer; from a copy of the same iterate after the slot moved on it
    computes one more, and the two runs agree bit for bit."""
    base = ExperimentSpec(method=method, data=data, reg=REG, q=q, eta=0.3,
                          batch_size=2, inner_steps=8, outer_iters=2)
    w = jax.block_until_ready(solve(base).w)
    copy = jnp.asarray(np.asarray(w))
    warm, carried = _counted(tmp_path / "warm", lambda: solve(
        base.replace(init_w=w, seed=1)))
    cold, computed = _counted(tmp_path / "cold", lambda: solve(
        base.replace(init_w=copy, seed=1)))
    lanes = _lanes(data, q or 1)
    assert carried == {"snapshot0.carried": 1, "full_grad.lanes": 2 * lanes,
                       "full_grad.stored": carried["full_grad.stored"]}
    assert computed["snapshot0.computed"] == 1
    assert computed["full_grad.lanes"] == 3 * lanes
    assert "snapshot0.carried" not in computed
    _assert_same_run(warm, cold)


def _one_bit_off(w):
    host = np.array(w)
    host.view(np.uint32)[-1] ^= 1
    return jnp.asarray(host)


@pytest.mark.parametrize("change", [
    "none", "other_data", "other_q", "other_loss", "other_method",
    "one_bit_off", "no_init_w",
])
def test_slot_carries_only_the_returned_iterate_on_the_same_key(
        tmp_path, data, change):
    """The slot hands its snapshot only to a call whose init_w holds the
    returned iterate's bits, on the same data object, method, partition
    and loss; each other call computes the full gradient at init_w."""
    base = ExperimentSpec(method="fdsvrg", data=data, reg=REG, q=2, eta=0.3,
                          batch_size=2, inner_steps=8, outer_iters=1)
    w = solve(base).w
    same = PaddedCSR(data.indices, data.values, data.labels, data.dim)
    spec = base.replace(**{"init_w": w, "seed": 1, **{
        "none": {},
        "other_data": {"data": same},
        "other_q": {"q": 4},
        "other_loss": {"loss": "squared_hinge"},
        "other_method": {"method": "serial", "q": None},
        "one_bit_off": {"init_w": _one_bit_off(w)},
        "no_init_w": {"init_w": None},
    }[change]})
    _, counters = _counted(tmp_path, lambda: solve(spec))
    hit = "snapshot0.carried" if change == "none" else "snapshot0.computed"
    assert counters[hit] == 1
    assert counters.get("snapshot0.carried", 0) + \
        counters.get("snapshot0.computed", 0) == 1


def test_slot_never_keeps_old_data_alive(data):
    """A call on other data empties the slot, whatever its method, and so
    does the collection of the data object it was filled on."""
    base = ExperimentSpec(method="fdsvrg", data=data, reg=REG, q=2, eta=0.3,
                          batch_size=2, inner_steps=8, outer_iters=1)
    solve(base)
    assert len(SNAPSHOT_SLOT) == 1
    other = make_sparse_classification(dim=64, num_instances=16,
                                       nnz_per_instance=4, seed=9)
    solve(ExperimentSpec(method="dsvrg", data=other, reg=REG, q=2,
                         inner_steps=4))
    assert len(SNAPSHOT_SLOT) == 0
    solve(base.replace(data=other))
    assert len(SNAPSHOT_SLOT) == 1
    BLOCK_CACHE.clear()
    del other
    gc.collect()
    assert len(SNAPSHOT_SLOT) == 0


# ---------------------------------------------------------------------------
# 6. the estimator
# ---------------------------------------------------------------------------


def test_estimator_fit_predict_score(data):
    clf = FDSVRGClassifier(method="fdsvrg", workers=2, eta=0.3, lam=1e-3,
                           batch_size=2, inner_steps=16, outer_iters=3)
    clf.fit(data)
    assert clf.n_features_in_ == data.dim
    assert clf.coef_.shape == (data.dim,)
    margins = clf.decision_function(data)
    assert margins.shape == (data.num_instances,)
    preds = clf.predict(data)
    assert set(np.unique(preds)) <= set(clf.classes_)
    assert clf.score(data) > 0.7  # planted separator: well above chance
    assert len(clf.history_) == 3


def test_estimator_partial_fit_warm_starts(data):
    clf = FDSVRGClassifier(method="serial", eta=0.3, lam=1e-3,
                           inner_steps=16, outer_iters=2)
    clf.fit(data)
    obj_after_fit = clf.final_objective()
    first_fit_obj = clf.history_[0].objective
    clf.partial_fit(data, outer_iters=2)
    assert len(clf.history_) == 4
    assert [h.outer for h in clf.history_] == [0, 1, 2, 3]
    # warm start: the continued run's FIRST outer already beats the cold
    # run's first outer (it starts from the fitted iterate)
    assert clf.history_[2].objective < first_fit_obj
    assert clf.final_objective() <= obj_after_fit + 1e-9
    # the cumulative fields read as ONE continuous run: no counter steps
    # backwards at the warm-start boundary
    for prev, cur in zip(clf.history_, clf.history_[1:]):
        assert cur.comm_scalars >= prev.comm_scalars
        assert cur.modeled_time_s >= prev.modeled_time_s
        assert cur.wall_time_s >= prev.wall_time_s
    # serving: the training-set memo is releasable
    assert clf.free_training_cache() is clf and clf._encoded is None
    assert clf.score(data) >= 0.0  # predict still works from coef_


def test_estimator_dense_input_and_label_mapping():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(24, 12)) * (rng.random((24, 12)) < 0.5)
    w_true = rng.normal(size=12)
    y = (X @ w_true > 0).astype(int)  # labels in {0, 1}
    if len(np.unique(y)) < 2:  # pragma: no cover - rng guard
        y[0] = 1 - y[0]
    clf = FDSVRGClassifier(method="serial", eta=0.5, lam=1e-4,
                           inner_steps=24, outer_iters=4)
    clf.fit(X, y)
    assert np.array_equal(clf.classes_, np.unique(y))
    preds = clf.predict(X)
    assert set(np.unique(preds)) <= set(clf.classes_)
    assert clf.score(X, y) > 0.7


def test_estimator_unfitted_raises():
    clf = FDSVRGClassifier()
    with pytest.raises(ValueError, match="not fitted"):
        clf.predict(np.zeros((2, 3)))


def test_as_padded_csr_roundtrip():
    X = np.array([[0.0, 1.5, 0.0, -2.0],
                  [3.0, 0.0, 0.0, 0.0],
                  [0.0, 0.0, 0.0, 0.0]])
    y = np.array([1.0, -1.0, 1.0])
    data = as_padded_csr(X, y)
    assert isinstance(data, PaddedCSR)
    assert data.dim == 4 and data.num_instances == 3
    np.testing.assert_array_equal(data.to_dense().T, X)


def test_as_padded_csr_roundtrip_random():
    """The vectorized pack (one np.nonzero, offset arithmetic) agrees
    with the dense oracle on ragged random sparsity."""
    rng = np.random.default_rng(7)
    X = rng.normal(size=(37, 23)).astype(np.float32)
    X[rng.random(X.shape) < 0.8] = 0.0
    data = as_padded_csr(X, np.where(rng.random(37) < 0.5, 1.0, -1.0))
    np.testing.assert_array_equal(data.to_dense().T, X)


def test_estimator_news20_end_to_end():
    """Acceptance: FDSVRGClassifier.fit(...).score(...) on news20."""
    from repro.data import datasets

    data = datasets.load("news20")
    clf = FDSVRGClassifier(method="fdsvrg", eta=2.0,
                           lam=2.0 / data.num_instances, outer_iters=2)
    clf.fit(data)
    assert clf.coef_.shape == (data.dim,)
    assert clf.score(data) > 0.6  # heavily regularized: above chance
    assert np.isfinite(clf.final_objective())


def _three_blobs(seed=0, per_class=30, dim=8):
    """Three well-separated Gaussian blobs; returns (X, y_int)."""
    rng = np.random.default_rng(seed)
    centers = np.eye(3, dim) * 6.0
    X = np.concatenate(
        [rng.normal(size=(per_class, dim)) + centers[c] for c in range(3)]
    )
    y = np.repeat(np.arange(3), per_class)
    return X, y


def test_estimator_multiclass_ovr_round_trip():
    """>2 classes: one-vs-rest through the multi-output driver path —
    string labels round-trip, coef_ is sklearn-shaped [k, d], and the
    blobs are easy enough that OvR must score near-perfectly."""
    X, y_int = _three_blobs()
    y = np.array(["ant", "bee", "cat"])[y_int]
    clf = FDSVRGClassifier(method="serial", eta=0.5, lam=1e-4,
                           inner_steps=64, outer_iters=6)
    clf.fit(X, y)
    np.testing.assert_array_equal(clf.classes_, ["ant", "bee", "cat"])
    assert clf.coef_.shape == (3, X.shape[1])
    df = clf.decision_function(X)
    assert df.shape == (X.shape[0], 3)
    preds = clf.predict(X)
    assert set(np.unique(preds)) <= {"ant", "bee", "cat"}
    assert clf.score(X, y) > 0.9


def test_estimator_ovr_column_bitwise_matches_binary_fit():
    """OvR column j == an independent binary fit of (class j vs rest),
    BITWISE: the multi-output driver vmaps one shared sample stream, so
    each column replays exactly the solve the binary path runs."""
    X, y = _three_blobs(seed=3)
    kw = dict(method="serial", eta=0.5, lam=1e-4,
              inner_steps=32, outer_iters=3)
    multi = FDSVRGClassifier(**kw).fit(X, y)
    for j, cls in enumerate(multi.classes_):
        binary = FDSVRGClassifier(**kw).fit(X, (y == cls).astype(int))
        # binary classes_ are [0, 1] -> +1 encodes class j, same as the
        # OvR column's +1
        np.testing.assert_array_equal(multi.coef_[j], binary.coef_)


def test_estimator_multiclass_partial_fit_warm_starts():
    X, y = _three_blobs(seed=5)
    clf = FDSVRGClassifier(method="serial", eta=0.5, lam=1e-4,
                           inner_steps=32, outer_iters=2)
    clf.fit(X, y)
    first = clf.history_[0].objective
    clf.partial_fit(X, y, outer_iters=2)
    assert clf.coef_.shape == (3, X.shape[1])
    # warm start: the continued run's first outer beats the cold first
    assert clf.history_[2].objective < first


@pytest.mark.parametrize("labels", ["binary", "multiclass"])
def test_estimator_partial_fit_carries_the_snapshot(tmp_path, data, labels):
    """coef_'s numpy round trip keeps the returned iterate's bits, so a
    second partial_fit on the same data starts from the snapshot the
    first one ended on."""
    if labels == "binary":
        X, y = data, None
    else:
        X, y = _three_blobs(seed=3)
    clf = FDSVRGClassifier(method="fdsvrg", workers=2, eta=0.3, lam=1e-3,
                           batch_size=2, inner_steps=8)
    clf.partial_fit(X, y)
    _, counters = _counted(tmp_path, lambda: clf.partial_fit(X, y))
    assert counters["snapshot0.carried"] == 1
    assert "snapshot0.computed" not in counters


def test_estimator_single_class_raises():
    X = np.ones((4, 3))
    clf = FDSVRGClassifier(method="serial")
    with pytest.raises(ValueError, match="at least 2 classes"):
        clf.fit(X, np.zeros(4))


# ---------------------------------------------------------------------------
# 7. LinearConfig.to_spec and the CLI entry point
# ---------------------------------------------------------------------------


def test_linear_config_to_spec():
    from repro.configs.fdsvrg_linear import CONFIGS

    lc = CONFIGS["fdsvrg-news20"]
    spec = lc.to_spec()
    assert spec.method == "fdsvrg"
    assert spec.dataset == "news20"
    assert spec.q == lc.workers == 8
    assert spec.reg == lc.regularizer()
    assert spec.eta == lc.eta
    spec2 = lc.to_spec(method="dsvrg", outer_iters=2, inner_steps=5)
    assert (spec2.method, spec2.outer_iters, spec2.inner_steps) == (
        "dsvrg", 2, 5)
    lc_l1 = CONFIGS["fdsvrg-webspam-l1"]
    assert lc_l1.to_spec().reg.name == "l1"


def test_cli_list_and_smoke(capsys, data):
    from repro.api import cli

    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in METHODS:
        assert name in out
    assert "multi_output" in out  # the capability matrix grows columns too
    assert cli.main([]) == 2  # --config required
    # capability/validation errors follow the same one-line convention
    assert cli.main(["--config", "fdsvrg-news20", "--method", "dsvrg",
                     "--use-kernels", "--quick"]) == 2
    assert "use_kernels" in capsys.readouterr().err
    assert cli.main(["--config", "fdsvrg-news20", "--method", "sgd"]) == 2


def test_run_method_shim_warns_deprecation(data):
    from benchmarks.common import run_method

    with pytest.warns(DeprecationWarning, match="repro.api.solve"):
        run_method("serial", data, 1, 1e-3, outer_iters=1)
