"""The comparison that decides ``correct``, driven through a whole run at
a CPU size with the chip check skipped: a sound run passes, and the
control and each fault the served path can have come out not correct."""
import jax.numpy as jnp

import tiny


def test_sound_run_is_correct():
    res = tiny.run()
    assert res["correct"], res["checks"]
    assert res["checks"]["jobs_compared"]["value"] >= 4
    assert res["failed"] == 0


def test_control_in_the_served_logits_place_is_not_correct():
    # the reference in bfloat16 in the served logits' place, decided by
    # the harness's own comparison
    res = tiny.run(control=True)
    assert not res["correct"]
    assert res["failed"] == res["checks"]["jobs_compared"]["value"] >= 4


def test_answer_altered_where_produced_is_not_correct():
    def plant(stage, last, payload):
        if not last:
            return payload
        return lambda x: payload(x) * jnp.float32(1.01)
    res = tiny.run(plant=plant)
    assert not res["correct"]
    assert res["failed"] > 0


def test_stage_returning_its_state_unchanged_is_not_correct():
    def plant(stage, last, payload):
        return (lambda x: x) if stage == 1 else payload
    res = tiny.run(plant=plant)
    assert not res["correct"]
    assert res["checks"]["payload_errors"]["value"] == 1
