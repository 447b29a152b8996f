"""The readers of the window counter and of the two pools' occupancy,
each on hand-made step records (``test_moe_metrics.py``'s manner)."""

import pytest

from perfbench import common


def reader(name):
    return common.load_file("layer_metrics", name).read


def step(op, **more):
    return {"op": op, "dur_s": 0.5, "tokens": 0, **more}


def pools(used_w, used_g, **more):
    return dict(pages_used=used_w + used_g, pages_total=3000,
                pages_used_window=used_w, pages_total_window=1000,
                pages_used_global=used_g, pages_total_global=2000,
                **more)


def test_window_bound_share_is_of_the_decode_slot_iterations():
    steps = [step("decode", slot_iters=256, window_bound_iters=192,
                  **pools(900, 800)),
             step("decode", slot_iters=64, window_bound_iters=0,
                  **pools(700, 500)),
             # A prefill launch counts its lanes; they are not decode
             # iterations.
             step("prefill", window_bound_iters=1, **pools(950, 820)),
             step("idle", **pools(100, 50))]
    obs = {"engine_steps": steps}
    assert reader("kv_cache.window_bound_share.decode")(obs) == \
        pytest.approx(60.0)
    # The fullest moment of each pool, whatever the step.
    assert reader("kv_cache.window_pool_peak_share.decode")(obs) == \
        pytest.approx(95.0)
    assert reader("kv_cache.global_pool_peak_share.decode")(obs) == \
        pytest.approx(41.0)
    # The accepted reader keeps its meaning: the sum over the sum.
    assert reader("kv_cache.pool_peak_share.decode")(obs) == \
        pytest.approx(100.0 * 1770 / 3000)


@pytest.mark.parametrize("name", [
    "kv_cache.window_bound_share.decode",
    "kv_cache.window_pool_peak_share.decode",
    "kv_cache.global_pool_peak_share.decode"])
def test_a_program_without_window_layers_gives_nothing(name):
    # The parent's records, and a model with one kind of layer.
    steps = [step("decode", slot_iters=16, slots_stepped=4, pages_used=5,
                  pages_total=10), step("prefill", pages_used=5,
                                        pages_total=10)]
    assert reader(name)({"engine_steps": steps}) is None
    assert reader(name)({"engine_steps": []}) is None
