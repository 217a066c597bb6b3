"""Drives a run of the ``serve_state`` cell at its rehearsal size with each
piece of the retention's mathematics planted wrong in the program, and sees
``correct`` come out false by the cell's limit - once for each fault of
``lib.serve_state.FAULTS`` - and true for the sound program, whose control (the
reference in fp8) reads over the limit too.

A state kept across requests is read only through the gates of the next
prompt: at normal(0.1) weights ``log g`` averages -0.9 a token, so after a
prompt of ``n`` tokens a predecessor's state is worth ``e^(-0.9 (n - 1))`` of
itself and the fault cannot be seen from the served tokens of a prompt of
tens.  Its run therefore offers prompts of one to four tokens (the rehearsal
mix with other lengths) and reads every finished request."""

import argparse
import copy
import time

import pytest

from lib import common, serve_state

CELL = "brumby-14b.serve-reason-surge"


def _run(seed=11, seconds=2.0, short_prompts=False, **kw):
    manifest, entry, cell, config = common.load_cell(CELL)
    if short_prompts:
        cell = copy.deepcopy(cell)
        cell["rehearse"]["traffic"]["prompt_tokens"] = {"dist": "uniform", "min": 1, "max": 4}
        cell["check_requests"] = 64
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0, rehearse=True, keep_trace=False)
    return serve_state.run(args, manifest, entry, cell, config, time.time(), **kw)


@pytest.mark.parametrize("short_prompts", [False, True], ids=["rehearsal_mix", "short_prompts"])
def test_sound_program_is_correct_and_control_reads_wider(short_prompts):
    line = _run(control="fp8", short_prompts=short_prompts)
    compared = line["compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
    assert compared["control_logit_gap"]["value"] > 100 * compared["served_logit_gap"]["limit"]


@pytest.mark.parametrize("fault", serve_state.FAULTS)
def test_planted_fault_is_not_correct(fault):
    line = _run(fault=fault, short_prompts=fault == "state_kept_across_requests")
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    # the state rounded to bfloat16 reads 2.6 to 10 times the limit, the others thousands
    room = 2 if fault == "state_in_bfloat16" else 100
    assert compared["served_logit_gap"]["value"] > room * compared["served_logit_gap"]["limit"]


def test_every_fault_is_planted_by_name():
    with pytest.raises(KeyError):
        with serve_state.planted("no_such_fault", {"retention": {}}):
            pass
