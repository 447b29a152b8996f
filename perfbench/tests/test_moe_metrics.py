"""The readers of the expert counters and of the prefill share, each on
hand-made step records (``test_program_metrics.py``'s manner)."""

import pytest

from perfbench import common


def reader(name):
    return common.load_file("layer_metrics", name).read


def step(op, dur_s=0.5, **more):
    return {"op": op, "dur_s": dur_s, "tokens": 0, **more}


def moe(picks_held, load_max, calls, held=64, **more):
    return step("decode", moe_picks=4 * picks_held,
                moe_picks_held=picks_held, moe_load_max=load_max,
                moe_layer_calls=calls, moe_expert_calls=calls * held,
                **more)


def test_expert_load_is_read_from_the_decode_steps_alone():
    steps = [moe(2048, 160, 32), moe(1024, 96, 16),
             # A prefill step's load is another matter: 1024 tokens.
             step("prefill", moe_picks=32768, moe_picks_held=8192,
                  moe_load_max=700, moe_layer_calls=4,
                  moe_expert_calls=256),
             step("idle")]
    obs = {"engine_steps": steps}
    # 3072 picks on held experts over 48 calls of 64 experts.
    assert reader("moe.tokens_per_held_expert.decode")(obs) == \
        pytest.approx(1.0)
    # The largest counts, a call: 256 in all, over a mean of 48 x 1.
    assert reader("moe.load_max_over_mean.decode")(obs) == \
        pytest.approx(256 / 48)


@pytest.mark.parametrize("name", ["moe.tokens_per_held_expert.decode",
                                  "moe.load_max_over_mean.decode"])
def test_a_program_without_expert_counters_gives_nothing(name):
    # The parent's records, and a model without experts.
    steps = [step("decode", slots_stepped=4), step("prefill")]
    assert reader(name)({"engine_steps": steps}) is None
    assert reader(name)({"engine_steps": []}) is None
    # An engine that decoded nothing the experts saw.
    assert reader(name)({"engine_steps": [moe(0, 0, 0)]}) is None


def test_prefill_time_share_is_of_the_steps_that_launched():
    steps = [step("decode", 0.6), step("prefill", 0.3),
             step("prefill", 0.1), step("idle", 5.0)]
    assert reader("engine.prefill_time_share.decode")(
        {"engine_steps": steps}) == pytest.approx(40.0)
    assert reader("engine.prefill_time_share.decode")(
        {"engine_steps": [step("idle")]}) is None
