"""Drives a run of the ``serve_mixed`` cell at its rehearsal size (float32,
window 16, pages of 4, a ring of 9 pages) with each new piece of the
mathematics planted wrong in the program, and sees ``correct`` come out false
by the cell's limit - once for each fault of ``lib.serve_mixed.FAULTS`` - and
true for the sound program, whose control (the reference in fp8) reads over
the limit too."""

import argparse
import time

import pytest

from lib import common, serve_mixed

CELL = "trinity-large.serve-longdoc-surge"


def _run(seed=11, seconds=2.0, **kw):
    manifest, entry, cell, config = common.load_cell(CELL)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0, rehearse=True, keep_trace=False)
    return serve_mixed.run(args, manifest, entry, cell, config, time.time(), **kw)


def test_sound_program_is_correct_and_control_reads_wider():
    line = _run(control="fp8")
    compared = line["compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
    assert compared["control_logit_gap_mean"]["value"] > 100 * compared["served_logit_gap_mean"]["limit"]


@pytest.mark.parametrize("fault", serve_mixed.FAULTS)
def test_planted_fault_is_not_correct(fault):
    line = _run(fault=fault)
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    # the bias is a fiftieth of a score: in the gates it reads 17 times the limit, the others hundreds
    room = 10 if fault == "bias_in_the_gates" else 100
    assert compared["served_logit_gap_mean"]["value"] > room * compared["served_logit_gap_mean"]["limit"]


def test_every_fault_is_planted_by_name():
    with pytest.raises(KeyError):
        with serve_mixed.planted("no_such_fault", {"experts": {}}):
            pass
