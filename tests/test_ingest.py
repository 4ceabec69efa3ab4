"""The out-of-core data path: streaming LibSVM -> per-worker BlockCSR.

The HARD CONTRACT under test: for any chunk size, worker count q, and
padding budget, the streamed build is bit-identical to the one-shot
``PaddedCSR -> BlockCSR.from_padded`` path — indices, values, nnz_col,
budgets, labels, all of it — so solver trajectories cannot depend on how
the data arrived.  Sections:

  * LibSVM text round-trip (writer -> parser, format edge cases)
  * label canonicalization conventions
  * chunked == one-shot bitwise (parametrized + hypothesis property)
  * on-disk slab cache: warm-hit equality, invalidation, atomicity keys
  * solve(): source= vs data= bit-parity end to end
  * datasets memory guard, deprecation shim
"""

import os
import warnings

import numpy as np
import pytest

from repro.core.partition import balanced
from repro.data import datasets
from repro.data.block_csr import BlockCSR
from repro.data.ingest_cache import get_or_build, load_block_csr
from repro.data.libsvm import (
    LibSVMFormatError,
    canonical_label_map,
    load_libsvm,
    scan_libsvm,
    write_libsvm,
)
from repro.data.pipeline import (
    ArraySource,
    LibSVMSource,
    SyntheticSource,
    as_source,
    is_source,
    source_labels,
    stream_block_csr,
    stream_block_slab,
    streamed_margins,
)
from repro.data.sparse import PaddedCSR
from repro.data.synthetic import make_sparse_classification

try:
    import hypothesis  # noqa: F401  (dev-only dep; see requirements-dev.txt)

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


def _data(dim=211, n=37, nnz=9, seed=0):
    return make_sparse_classification(
        dim=dim, num_instances=n, nnz_per_instance=nnz, seed=seed
    )


def _assert_blocks_equal(a: BlockCSR, b: BlockCSR) -> None:
    """Bitwise equality of every field the solvers can observe."""
    assert a.partition.bounds == b.partition.bounds
    assert a.nnz_budgets == b.nnz_budgets
    assert a.global_nnz_max() == b.global_nnz_max()
    # Every way of making the layout counts its stored entries on the host.
    assert a.stored == b.stored == sum(
        int(np.count_nonzero(np.asarray(v))) for v in a.values)
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))
    for l in range(a.num_blocks):
        np.testing.assert_array_equal(
            np.asarray(a.indices[l]), np.asarray(b.indices[l])
        )
        np.testing.assert_array_equal(
            np.asarray(a.values[l]), np.asarray(b.values[l])
        )
        np.testing.assert_array_equal(
            np.asarray(a.nnz_col[l]), np.asarray(b.nnz_col[l])
        )
        assert a.nnz_col[l].dtype == b.nnz_col[l].dtype


# ---------------------------------------------------------------------------
# LibSVM text round-trip
# ---------------------------------------------------------------------------


def test_write_load_round_trip_exact(tmp_path):
    data = _data(seed=3)
    path = str(tmp_path / "rt.libsvm")
    write_libsvm(path, data)
    back = load_libsvm(path, dim=data.dim)
    assert back.dim == data.dim
    assert back.num_instances == data.num_instances
    np.testing.assert_array_equal(
        np.asarray(back.labels), np.asarray(data.labels)
    )
    # stored entries round-trip exactly (repr() float32 text contract);
    # compare as (id, value) sets per row — padding layout may differ
    src_idx, src_val = np.asarray(data.indices), np.asarray(data.values)
    got_idx, got_val = np.asarray(back.indices), np.asarray(back.values)
    for i in range(data.num_instances):
        want = sorted(
            (int(j), float(v))
            for j, v in zip(src_idx[i], src_val[i])
            if v != 0.0
        )
        got = sorted(
            (int(j), float(v))
            for j, v in zip(got_idx[i], got_val[i])
            if v != 0.0
        )
        assert got == want, f"row {i}"


def test_parser_comments_blanks_empty_rows_and_qid(tmp_path):
    path = str(tmp_path / "edge.libsvm")
    with open(path, "w") as f:
        f.write("# leading comment\n")
        f.write("+1 1:0.5 3:1.25 # trailing comment\n")
        f.write("\n")  # blank line skipped
        f.write("-1\n")  # empty row: label only, no features
        f.write("-1 qid:7 2:2.0\n")  # qid token skipped
    data = load_libsvm(path)
    assert data.num_instances == 3
    assert data.dim == 3  # 1-based "3:" is 0-based id 2, so dim = 3
    np.testing.assert_array_equal(
        np.asarray(data.labels), np.asarray([1.0, -1.0, -1.0], np.float32)
    )
    dense = np.asarray(data.to_dense())  # (dim, n)
    np.testing.assert_allclose(dense[:, 0], [0.5, 0.0, 1.25])
    np.testing.assert_allclose(dense[:, 1], [0.0, 0.0, 0.0])
    np.testing.assert_allclose(dense[:, 2], [0.0, 2.0, 0.0])


def test_parser_duplicate_ids_preserved_in_file_order(tmp_path):
    """Duplicate feature ids stay as separate stored entries in file
    order — the scatter program-order contract (last write wins for
    gather, sum for scatter) must see them exactly as written."""
    path = str(tmp_path / "dup.libsvm")
    with open(path, "w") as f:
        f.write("+1 2:1.0 2:3.0 1:0.5\n")
    data = load_libsvm(path)
    idx, val = np.asarray(data.indices[0]), np.asarray(data.values[0])
    stored = [(int(i), float(v)) for i, v in zip(idx, val) if v != 0.0]
    assert stored == [(1, 1.0), (1, 3.0), (0, 0.5)]


def test_parser_rejects_malformed(tmp_path):
    for bad in ("+1 0:1.0\n", "+1 3:not_a_float\n", "+1 3\n"):
        path = str(tmp_path / "bad.libsvm")
        with open(path, "w") as f:
            f.write(bad)
        with pytest.raises(LibSVMFormatError):
            load_libsvm(path)


def test_scan_matches_load(tmp_path):
    data = _data(seed=11)
    path = str(tmp_path / "scan.libsvm")
    write_libsvm(path, data)
    stats = scan_libsvm(path)
    loaded = load_libsvm(path)
    assert stats.num_instances == loaded.num_instances
    assert stats.max_index + 1 == loaded.dim
    assert stats.nnz_max == loaded.nnz_max


def test_writer_emits_one_based_indices(tmp_path):
    data = PaddedCSR(
        indices=np.asarray([[0, 2, 0]], np.int32),
        values=np.asarray([[1.5, 2.5, 0.0]], np.float32),
        labels=np.asarray([1.0], np.float32),
        dim=3,
    )
    path = str(tmp_path / "one.libsvm")
    write_libsvm(path, data)
    with open(path) as f:
        line = f.read().strip()
    assert line == "1 1:1.5 3:2.5"


# ---------------------------------------------------------------------------
# label conventions
# ---------------------------------------------------------------------------


def test_labels_plus_minus_one_pass_through():
    m = canonical_label_map((-1.0, 1.0))
    np.testing.assert_array_equal(
        m(np.asarray([1.0, -1.0, 1.0])), [1.0, -1.0, 1.0]
    )


def test_labels_zero_one_maps_zero_to_minus_one():
    m = canonical_label_map((0.0, 1.0))
    np.testing.assert_array_equal(m(np.asarray([0.0, 1.0])), [-1.0, 1.0])


def test_labels_arbitrary_pair_sorted_high_is_positive():
    m = canonical_label_map((3.0, 7.0))
    np.testing.assert_array_equal(
        m(np.asarray([7.0, 3.0, 7.0])), [1.0, -1.0, 1.0]
    )


def test_labels_reject_multiclass_and_odd_singleton():
    with pytest.raises(ValueError, match="binary"):
        canonical_label_map((1.0, 2.0, 3.0))
    with pytest.raises(ValueError, match="single label"):
        canonical_label_map((5.0,))


def test_labels_single_standard_value_ok():
    m = canonical_label_map((1.0,))
    np.testing.assert_array_equal(m(np.asarray([1.0, 1.0])), [1.0, 1.0])


def test_labels_zero_one_from_file(tmp_path):
    path = str(tmp_path / "zo.libsvm")
    with open(path, "w") as f:
        f.write("0 1:1.0\n1 2:1.0\n0 1:2.0\n")
    data = load_libsvm(path)
    np.testing.assert_array_equal(
        np.asarray(data.labels), np.asarray([-1.0, 1.0, -1.0], np.float32)
    )


# ---------------------------------------------------------------------------
# chunked == one-shot, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk_rows", [1, 3, 7, 16, 1000])
@pytest.mark.parametrize("q", [1, 3, 8])
def test_array_source_streamed_equals_from_padded(q, chunk_rows):
    data = _data(seed=q)
    part = balanced(data.dim, q)
    want = BlockCSR.from_padded(data, part)
    got = stream_block_csr(
        ArraySource(data), part, chunk_rows=chunk_rows
    )
    _assert_blocks_equal(got, want)


@pytest.mark.parametrize("lane_multiple", [1, 8])
@pytest.mark.parametrize("q", [1, 4])
def test_lane_multiple_budgets_match(q, lane_multiple):
    data = _data(seed=2)
    part = balanced(data.dim, q)
    want = BlockCSR.from_padded(data, part, lane_multiple=lane_multiple)
    got = stream_block_csr(
        ArraySource(data), part, chunk_rows=5, lane_multiple=lane_multiple
    )
    _assert_blocks_equal(got, want)


@pytest.mark.parametrize("chunk_rows", [1, 13, 4096])
def test_libsvm_source_streamed_equals_oneshot(tmp_path, chunk_rows):
    data = _data(seed=5)
    path = str(tmp_path / "eq.libsvm")
    write_libsvm(path, data)
    src = LibSVMSource(path, dim=data.dim)
    part = balanced(data.dim, 4)
    want = BlockCSR.from_padded(load_libsvm(path, dim=data.dim), part)
    got = stream_block_csr(src, part, chunk_rows=chunk_rows)
    _assert_blocks_equal(got, want)


def test_explicit_zeros_streamed_like_oneshot():
    """from_padded drops value==0 stored entries for q>1 and keeps rows
    verbatim for q==1; the streamed build must mirror both behaviors."""
    idx = np.asarray([[0, 5, 9], [3, 3, 0]], np.int32)
    val = np.asarray([[1.0, 0.0, 2.0], [4.0, 5.0, 0.0]], np.float32)
    data = PaddedCSR(
        indices=idx, values=val,
        labels=np.asarray([1.0, -1.0], np.float32), dim=10,
    )
    for q in (1, 2, 3):
        part = balanced(10, q)
        _assert_blocks_equal(
            stream_block_csr(ArraySource(data), part, chunk_rows=1),
            BlockCSR.from_padded(data, part),
        )


def test_single_slab_matches_full_build():
    data = _data(seed=9)
    part = balanced(data.dim, 5)
    full = stream_block_csr(ArraySource(data), part, chunk_rows=7)
    for l in range(5):
        idx, val, nnz_col = stream_block_slab(
            ArraySource(data), part, l, chunk_rows=7
        )
        np.testing.assert_array_equal(idx, np.asarray(full.indices[l]))
        np.testing.assert_array_equal(val, np.asarray(full.values[l]))
        np.testing.assert_array_equal(nnz_col, np.asarray(full.nnz_col[l]))
        assert idx.shape[1] == full.nnz_budgets[l]


def test_synthetic_source_matches_datasets_load():
    src = SyntheticSource.from_dataset("news20", seed=0)
    data = datasets.load("news20", seed=0)
    part = balanced(data.dim, 4)
    _assert_blocks_equal(
        stream_block_csr(src, part, chunk_rows=999),
        BlockCSR.from_padded(data, part),
    )


def test_streamed_margins_match_dense_oracle():
    data = _data(seed=13)
    rng = np.random.default_rng(0)
    w = rng.normal(size=data.dim).astype(np.float32)
    got = streamed_margins(ArraySource(data), w, chunk_rows=5)
    want = np.asarray(data.to_dense()).T @ w
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_source_labels_and_as_source_coercion(tmp_path):
    data = _data(seed=1)
    np.testing.assert_array_equal(
        source_labels(ArraySource(data), chunk_rows=4),
        np.asarray(data.labels),
    )
    assert is_source(as_source(data))
    path = str(tmp_path / "c.libsvm")
    write_libsvm(path, data)
    src = as_source(path)
    assert isinstance(src, LibSVMSource)
    assert as_source(src) is src
    with pytest.raises(TypeError):
        as_source(42)


@pytest.mark.parametrize("chunk_rows", [1, 5, 12, 36, 100])
def test_streamed_helpers_chunk_boundaries(chunk_rows):
    """Chunk size must be invisible: chunk_rows=1, an exact divisor of N
    (empty tail), and chunk_rows > N all give the same answers."""
    data = _data(n=36, seed=21)  # 36 rows: 12 and 36 divide exactly
    rng = np.random.default_rng(2)
    w = rng.normal(size=data.dim).astype(np.float32)
    np.testing.assert_array_equal(
        streamed_margins(ArraySource(data), w, chunk_rows=chunk_rows),
        streamed_margins(ArraySource(data), w, chunk_rows=36),
    )
    np.testing.assert_array_equal(
        source_labels(ArraySource(data), chunk_rows=chunk_rows),
        np.asarray(data.labels),
    )


def test_streamed_margins_multioutput_matches_per_column():
    """[d, k] weights stream in ONE pass, each column bit-identical to
    the k = 1 call with that column."""
    data = _data(seed=23)
    rng = np.random.default_rng(3)
    w = rng.normal(size=(data.dim, 3)).astype(np.float32)
    got = streamed_margins(ArraySource(data), w, chunk_rows=7)
    assert got.shape == (37, 3)
    for j in range(3):
        np.testing.assert_array_equal(
            got[:, j],
            streamed_margins(ArraySource(data), w[:, j], chunk_rows=7),
        )
    with pytest.raises(ValueError, match=r"\[d\] or \[d, k\]"):
        streamed_margins(ArraySource(data), w[None], chunk_rows=7)


def test_streamed_margins_empty_source():
    empty = PaddedCSR(
        indices=np.zeros((0, 4), np.int32),
        values=np.zeros((0, 4), np.float32),
        labels=np.zeros((0,), np.float32),
        dim=11,
    )
    w = np.ones(11, np.float32)
    assert streamed_margins(ArraySource(empty), w).shape == (0,)
    w2 = np.ones((11, 2), np.float32)
    assert streamed_margins(ArraySource(empty), w2).shape == (0, 2)
    assert source_labels(ArraySource(empty)).shape == (0,)


def test_libsvm_dim_override_too_small_is_one_line_error(tmp_path):
    data = _data(seed=4)
    path = str(tmp_path / "d.libsvm")
    write_libsvm(path, data)
    max_id = int(np.asarray(data.indices).max())
    with pytest.raises(ValueError, match=f"feature id {max_id}") as exc:
        LibSVMSource(path, dim=max_id).stats()
    assert "\n" not in str(exc.value)
    # and the boundary value (max id + 1) is accepted
    assert LibSVMSource(path, dim=max_id + 1).stats().dim == max_id + 1


if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @given(
        st.integers(min_value=1, max_value=9),
        st.integers(min_value=1, max_value=50),
        st.sampled_from([1, 8]),
        st.integers(min_value=0, max_value=10_000),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_chunked_equals_oneshot(q, chunk_rows, lane, seed):
        data = _data(dim=97, n=23, nnz=6, seed=seed % 17)
        part = balanced(data.dim, q)
        want = BlockCSR.from_padded(data, part, lane_multiple=lane)
        got = stream_block_csr(
            ArraySource(data), part,
            chunk_rows=chunk_rows, lane_multiple=lane,
        )
        _assert_blocks_equal(got, want)


# ---------------------------------------------------------------------------
# on-disk slab cache
# ---------------------------------------------------------------------------


def test_cache_cold_then_warm_bitwise(tmp_path):
    data = _data(seed=21)
    path = str(tmp_path / "c.libsvm")
    write_libsvm(path, data)
    cache = str(tmp_path / "cache")
    part = balanced(data.dim, 3)

    cold = get_or_build(
        LibSVMSource(path, dim=data.dim), part, cache_dir=cache
    )
    assert cold.status == "cold"
    warm = get_or_build(
        LibSVMSource(path, dim=data.dim), part, cache_dir=cache
    )
    assert warm.status == "warm"
    assert warm.path == cold.path
    _assert_blocks_equal(warm.data, cold.data)
    _assert_blocks_equal(
        cold.data, BlockCSR.from_padded(load_libsvm(path, dim=data.dim), part)
    )


def test_cache_off_without_dir():
    data = _data(seed=22)
    out = get_or_build(ArraySource(data), balanced(data.dim, 2),
                       cache_dir=None)
    assert out.status == "off"
    assert out.path is None


def test_cache_invalidates_when_file_changes(tmp_path):
    data = _data(seed=23)
    path = str(tmp_path / "c.libsvm")
    write_libsvm(path, data)
    cache = str(tmp_path / "cache")
    part = balanced(data.dim, 2)
    first = get_or_build(LibSVMSource(path, dim=data.dim), part,
                         cache_dir=cache)
    # rewrite with different contents (flip one label) -> digest moves
    flipped = PaddedCSR(
        indices=data.indices, values=data.values,
        labels=np.asarray(-np.asarray(data.labels)), dim=data.dim,
    )
    write_libsvm(path, flipped)
    os.utime(path, ns=(1, 1))  # defeat any mtime-based memoization
    second = get_or_build(LibSVMSource(path, dim=data.dim), part,
                          cache_dir=cache)
    assert second.status == "cold"
    assert second.path != first.path
    np.testing.assert_array_equal(
        np.asarray(second.data.labels), -np.asarray(first.data.labels)
    )


def test_cache_keyed_on_partition_and_lane(tmp_path):
    data = _data(seed=24)
    cache = str(tmp_path / "cache")
    src = ArraySource(data)
    a = get_or_build(src, balanced(data.dim, 2), cache_dir=cache)
    b = get_or_build(src, balanced(data.dim, 3), cache_dir=cache)
    c = get_or_build(src, balanced(data.dim, 2), cache_dir=cache,
                     lane_multiple=8)
    assert len({a.path, b.path, c.path}) == 3
    assert all(o.status == "cold" for o in (a, b, c))


def test_cache_same_bytes_for_any_chunking(tmp_path):
    """chunk_rows is NOT part of the cache key: the build is bit-identical
    for any chunking, so a cache written at one chunk size warm-hits a
    read at another."""
    data = _data(seed=25)
    cache = str(tmp_path / "cache")
    src = ArraySource(data)
    part = balanced(data.dim, 4)
    cold = get_or_build(src, part, cache_dir=cache, chunk_rows=3)
    warm = get_or_build(src, part, cache_dir=cache, chunk_rows=1000)
    assert cold.status == "cold" and warm.status == "warm"
    _assert_blocks_equal(cold.data, warm.data)


def test_cache_load_rejects_version_and_digest_mismatch(tmp_path):
    import json

    data = _data(seed=26)
    cache = str(tmp_path / "cache")
    src = ArraySource(data)
    part = balanced(data.dim, 2)
    out = get_or_build(src, part, cache_dir=cache)
    manifest = os.path.join(out.path, "manifest.json")
    with open(manifest) as f:
        m = json.load(f)
    m["digest"] = "tampered"
    with open(manifest, "w") as f:
        json.dump(m, f)
    assert load_block_csr(cache, src.digest(), part) is None


def test_cache_slabs_compressed_and_trimmed(tmp_path):
    """v2 format: slabs are deflated npz with trailing all-padding lanes
    dropped on disk, and the load re-pads to the exact in-memory layout."""
    import zipfile

    data = _data(seed=27)
    cache = str(tmp_path / "cache")
    part = balanced(data.dim, 2)
    # lane_multiple=8 rounds every slab's lane count up, guaranteeing
    # trailing pure-padding lanes for the trim to remove
    cold = get_or_build(ArraySource(data), part, cache_dir=cache,
                        lane_multiple=8)
    trimmed_any = False
    for l in range(2):
        slab_path = os.path.join(cold.path, f"slab_{l:04d}.npz")
        with zipfile.ZipFile(slab_path) as zf:
            assert all(i.compress_type == zipfile.ZIP_DEFLATED
                       for i in zf.infolist())
        with np.load(slab_path) as slab:
            lanes = int(slab["lanes"])
            assert lanes == np.asarray(cold.data.indices[l]).shape[1]
            assert slab["indices"].shape == slab["values"].shape
            assert slab["indices"].shape[1] <= lanes
            trimmed_any |= slab["indices"].shape[1] < lanes
    assert trimmed_any  # the rounded-up lanes really were dropped on disk
    warm = get_or_build(ArraySource(data), part, cache_dir=cache,
                        lane_multiple=8)
    assert warm.status == "warm"
    _assert_blocks_equal(warm.data, cold.data)


def test_cache_old_format_version_is_rebuilt(tmp_path, monkeypatch):
    """A v1-era entry (uncompressed, no lane trim) is never trusted: the
    format version is part of the key, so the current code cold-rebuilds
    beside it — and even a same-key manifest claiming an old version is
    refused by the load."""
    import json

    from repro.data import ingest_cache

    data = _data(seed=28)
    cache = str(tmp_path / "cache")
    src = ArraySource(data)
    part = balanced(data.dim, 2)
    monkeypatch.setattr(ingest_cache, "CACHE_VERSION", 1)
    old = get_or_build(src, part, cache_dir=cache)
    assert old.status == "cold"
    monkeypatch.undo()
    new = get_or_build(src, part, cache_dir=cache)
    assert new.status == "cold" and new.path != old.path
    _assert_blocks_equal(new.data, old.data)
    manifest = os.path.join(new.path, "manifest.json")
    with open(manifest) as f:
        m = json.load(f)
    m["version"] = 1
    with open(manifest, "w") as f:
        json.dump(m, f)
    assert load_block_csr(cache, src.digest(), part) is None


# ---------------------------------------------------------------------------
# solve(): source= vs data= bit-parity end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["serial", "fdsvrg", "fdsvrg_sim"])
def test_solve_source_bitwise_matches_in_memory(tmp_path, method):
    from repro.api import ExperimentSpec, solve

    data = _data(dim=157, n=29, nnz=7, seed=31)
    path = str(tmp_path / "s.libsvm")
    write_libsvm(path, data)
    common = dict(
        method=method, outer_iters=2, inner_steps=40,
        q=3 if method != "serial" else None,
    )
    r_mem = solve(ExperimentSpec(data=load_libsvm(path), **common))
    r_src = solve(ExperimentSpec(
        source=path, ingest_chunk_rows=11,
        data_cache_dir=str(tmp_path / "cache"), **common,
    ))
    np.testing.assert_array_equal(
        np.asarray(r_mem.w), np.asarray(r_src.w)
    )
    for a, b in zip(r_mem.history, r_src.history):
        assert a.objective == b.objective
        assert a.grad_norm == b.grad_norm
        assert a.comm_scalars == b.comm_scalars
        assert a.modeled_time_s == b.modeled_time_s


def test_solve_rejects_source_for_non_streaming_method(tmp_path):
    from repro.api import ExperimentSpec, solve

    data = _data(seed=32)
    path = str(tmp_path / "s.libsvm")
    write_libsvm(path, data)
    with pytest.raises(ValueError, match="stream"):
        solve(ExperimentSpec(source=path, method="dsvrg", outer_iters=1))


def test_spec_requires_exactly_one_input(tmp_path):
    from repro.api import ExperimentSpec

    data = _data(seed=33)
    with pytest.raises(ValueError):
        ExperimentSpec(method="fdsvrg")  # none of dataset/data/source
    with pytest.raises(ValueError):
        ExperimentSpec(method="fdsvrg", dataset="news20", source="x.libsvm")
    with pytest.raises(ValueError):
        ExperimentSpec(method="fdsvrg", data=data,
                       data_cache_dir="c")  # cache needs a source


def test_estimator_fits_from_path(tmp_path):
    from repro.api import FDSVRGClassifier

    data = _data(dim=157, n=40, nnz=7, seed=34)
    path = str(tmp_path / "e.libsvm")
    write_libsvm(path, data)
    clf = FDSVRGClassifier(
        method="fdsvrg", workers=3, outer_iters=2, inner_steps=40,
        data_cache_dir=str(tmp_path / "cache"),
    )
    clf.fit(path)
    assert clf.n_features_in_ == load_libsvm(path).dim
    margins = clf.decision_function(path)
    assert margins.shape == (data.num_instances,)
    assert 0.0 <= clf.score(path) <= 1.0
    with pytest.raises(ValueError, match="y"):
        clf.fit(path, y=np.asarray(data.labels))


# ---------------------------------------------------------------------------
# datasets memory guard + deprecation shim
# ---------------------------------------------------------------------------


def test_datasets_guard_blocks_oversized_materialize():
    with pytest.raises(MemoryError, match="SyntheticSource"):
        datasets.load("webspam", scaled=False)


def test_datasets_guard_respects_budget_override():
    spec = datasets.spec("webspam", scaled=False)
    assert datasets.materialize_bytes(spec) > (1 << 30)
    # scaled presets stay well under the default budget
    assert datasets.materialize_bytes(datasets.spec("webspam")) < (1 << 30)


def test_token_stream_shim_warns():
    import repro.data.pipeline as pipeline_mod

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = pipeline_mod.PipelineConfig
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    from repro.data.token_stream import PipelineConfig

    assert cfg is PipelineConfig
    with pytest.raises(AttributeError):
        pipeline_mod.does_not_exist
