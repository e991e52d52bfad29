"""Peaks and the necessary bytes of a batch."""
from __future__ import annotations

import pytest

from bench import cells, harness, roofline

PAPER = roofline.TableShape(slots=1 << 26, max_probes=128, chunk=4096)


def _bytes(shape, traffic, keys):
    """Necessary bytes of a batch of mix ``traffic`` (op counts from its
    generator)."""
    mix = cells.traffic(traffic)
    t = harness.traffic(cells.Cell(traffic, {"keys": 4096}, mix, 1, (), ()),
                        1)
    return roofline.batch_bytes(shape, t.ops, keys, bool(mix.get("rehash")))


@pytest.mark.parametrize("traffic, want", [
    # 65536 lookups x (key + state windows of 2 rows + a value row)
    # + 65536 x 9 B of inputs and outputs
    ("ycsb_c.zipf", 65536 * (2 * 2 * 512 + 512) + 65536 * 9),
    # + 7168 claims (window + 3 rows written) + 8192 delete probes and
    # 7168 state rows + update inputs and outputs
    ("paper_mix.steady", 65536 * 2560 + 7168 * (2048 + 1536)
     + 8192 * 2048 + 7168 * 512 + 65536 * 9 + 8192 * 10 + 8192 * 6),
    # old and new table for lookups and deletes, the hazard buffer read by
    # both, the claim in the new table, and half an extract (chunk read,
    # states marked, hazard written) plus half a landing of 2048 entries
    ("paper_mix.rehash", 65536 * 2 * 2560 + 2 * 4096 * 9
     + 7168 * (2048 + 1536) + 8192 * 2 * 2048 + 7168 * 512
     + (4096 * 16 + 4096 * 9 + 2048 * (2048 + 1536) + 4096 * 9) // 2
     + 65536 * 9 + 8192 * 10 + 8192 * 6),
])
def test_batch_bytes_of_each_cell(traffic, want):
    assert _bytes(PAPER, traffic, 1 << 25) == want


def test_paper_rehash_reads_about_three_eighths_of_a_gib():
    got = _bytes(PAPER, "paper_mix.rehash", 1 << 25)
    assert 0.37 < got / 2**30 < 0.38


def test_window_rows():
    assert [roofline.window_rows(p) for p in (1, 2, 128, 129, 130)] \
        == [1, 2, 2, 2, 3]


@pytest.mark.parametrize("traffic", ["paper_mix.rehash", "ycsb_c.zipf"])
def test_bytes_do_not_depend_on_the_op_set(traffic):
    from repro.core import dhash
    shapes = {roofline.table_shape(dhash.make("linear", capacity=3000,
                                              chunk=256, fused=fused))
              for fused in (False, True)}
    assert len(shapes) == 1
    assert _bytes(shapes.pop(), traffic, 3000) > 0


def test_peaks_by_device_kind():
    p = roofline.peak("TPU v5 lite")
    assert (p.hbm_bytes_per_s, p.hbm_bytes, p.bf16_flops_per_s) \
        == (819e9, 16e9, 197e12)
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peak("cpu")
