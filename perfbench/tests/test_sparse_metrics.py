"""The two readers of the learned selection's counters, each on
hand-made step records (``test_window_metrics.py``'s manner)."""

import pytest

from perfbench import common


def reader(name):
    return common.load_file("layer_metrics", name).read


def step(op, **more):
    return {"op": op, "dur_s": 0.5, "tokens": 0, **more}


def test_the_shares_are_of_the_decode_records():
    steps = [step("decode", slot_iters=256, sparse_bound_iters=192,
                  index_keys_scored=4_000_000, index_keys_kept=900_000),
             step("decode", slot_iters=64, sparse_bound_iters=0,
                  index_keys_scored=100_000, index_keys_kept=100_000),
             # A prefill launch counts its lanes and its chunk's
             # queries; they are not decode iterations.
             step("prefill", sparse_bound_iters=1,
                  index_keys_scored=9_000_000, index_keys_kept=2_000_000),
             step("idle")]
    obs = {"engine_steps": steps}
    assert reader("attn.sparse_bound_share.decode")(obs) == \
        pytest.approx(60.0)
    assert reader("attn.kept_key_share.decode")(obs) == \
        pytest.approx(1_000_000 / 4_100_000)


@pytest.mark.parametrize("name", ["attn.sparse_bound_share.decode",
                                  "attn.kept_key_share.decode"])
def test_a_program_without_a_selection_gives_nothing(name):
    # The parent's records, and a model without an indexer.
    steps = [step("decode", slot_iters=16, slots_stepped=4,
                  window_bound_iters=3, pages_used=5, pages_total=10),
             step("prefill", pages_used=5, pages_total=10)]
    assert reader(name)({"engine_steps": steps}) is None
    assert reader(name)({"engine_steps": []}) is None
