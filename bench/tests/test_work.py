"""Required work depends on the cell's shapes alone: not on padding, not
on how the program lays the work out."""

import copy

import pytest

from harness import core, work


def test_news20_outer_is_about_245_mb():
    b = work.svrg_outer_bytes(dim=1355191, n=19954, nnz_total=19954 * 455,
                              u=8, m=2494)
    assert b == pytest.approx(245e6, rel=0.02)
    assert work.svrg_outer_rows(n=19954, u=8, m=2494) == 19954 + 2494 * 8


def test_margin_bytes_count_stored_ids_only():
    assert work.margin_bytes(15 * 256, 256) == 12 * 15 * 256 + 4 * 256


def test_roofline_share_names_its_bound():
    peaks = work.load_peaks("TPU v5 lite")
    share, bound = work.roofline_share(1.0, 819e9, 2.0, peaks)
    assert (share, bound) == (pytest.approx(50.0), "bytes")
    share, bound = work.roofline_share(197e12, 1.0, 4.0, peaks)
    assert (share, bound) == (pytest.approx(25.0), "flops")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("cpu")


def test_training_work_ignores_the_block_layout(run_cell):
    """q = 2 pads each block's rows to its own width; the count of an
    outer's work stays that of q = 1."""
    counts = []
    for q in (1, 2):
        from conftest import small_cell

        cell = small_cell("train.news20")
        cell.traffic["q"] = q
        run = run_cell("train.news20", cell=cell, seconds=0.3)
        counts.append((run.counts["outer_bytes"], run.counts["outer_flops"]))
    assert counts[0] == counts[1]


def test_serving_work_ignores_bucket_padding(run_cell):
    """A wider minimum bucket pads every request further; the stored ids
    counted stay the same."""
    from conftest import small_cell

    got = []
    for min_width in (8, 64):
        cell = small_cell("serve.avazu.bulk")
        cell.traffic = copy.deepcopy(cell.traffic)
        cell.traffic["batcher"]["min_width"] = min_width
        run = run_cell("serve.avazu.bulk", cell=cell, seconds=0.3)
        got.append(run.counts["stored_ids"] / run.counts["completed"])
    assert got == [15.0, 15.0]


def test_cell_shapes_come_from_the_configuration():
    cell = core.load_cell("train.news20")
    sh = cell.driver.shapes(cell.config, cell.traffic)
    assert sh == {"dim": 1355191, "n": 19954, "nnz_total": 19954 * 455,
                  "u": 8, "m": 2494}


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_training_rows_keep_the_published_total(seed):
    """Row lengths are heavy-tailed, the same set for every seed, and sum
    to the count the work is figured from."""
    import numpy as np

    from conftest import small_cell

    cell = small_cell("train.news20")
    ids, vals, _ = cell.rows.dataset(cell.config, seed)
    stored = np.count_nonzero(np.asarray(vals), axis=1)
    sh = cell.driver.shapes(cell.config, cell.traffic)
    assert stored.sum() == sh["nnz_total"]
    assert stored.max() > 2 * np.median(stored)
    other, _, _ = cell.rows.dataset(cell.config, seed + 1)
    assert sorted(stored) == sorted(np.count_nonzero(np.asarray(other), axis=1))


def test_news20_lengths_sum_to_table_1():
    from harness import lengths

    cfg = core.load_json(core.BENCH / "configs" / "news20.json")
    n, per_row = cfg["num_instances"], cfg["nnz_per_row"]
    got = lengths.draw(cfg["lengths"], n, 5, fixed=per_row, total=n * per_row)
    assert got.sum() == 9_079_070 and got.min() == 129 and got.max() == 1024
