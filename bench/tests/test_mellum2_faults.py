"""Drives a run of the Mellum2 ``serve_mixed`` cell at its rehearsal size
(float32, window 16, pages of 4, a ring of 9 pages, 32 experts top 8 all held,
YaRN over 64 original positions) with each piece of the new mathematics
planted wrong in the program, and sees ``correct`` come out false by the
cell's limit - once for each fault of :data:`FAULTS` - and true for the sound
program, whose control (the reference in fp8) reads over the limit too.

The faults are this model's own; ``lib/serve_mixed.py``'s are Trinity's (a
gate, sandwich norms, a biased sigmoid router, no rope on the full layers),
most of which this model has nothing to leave out.  :func:`planted` has the
signature of ``serve_mixed.planted``, so a run on the chip reads each fault
through ``bench/limits.py`` with ``serve_mixed.planted`` and ``FAULTS``
replaced by these (``BENCH_MIXED_FAULTS=all``)."""

import argparse
import contextlib
import json
import time

import pytest

from lib import common, serve_mixed
from lib.serve_arch import _patched

CELL = "mellum2-12b.serve-code-surge"


def _fault_kinds_rope_swapped(original):
    def apply_rope(x, positions, cfg, rope=None):
        # a window layer (no rope of its own) takes the full layers' YaRN,
        # a full layer the window layers' plain rope
        return original(x, positions, cfg, cfg.full_rope if rope is None else None)
    return apply_rope


@contextlib.contextmanager
def planted(name, fields):
    """The program with one piece of the new mathematics wrong, for the run
    inside the ``with``; yields the ``transformer`` fields to build it from."""
    from accelerate_tpu.models import transformer
    from accelerate_tpu.serving import paging

    fields = json.loads(json.dumps(fields))
    ctx = contextlib.nullcontext()
    if name == "yarn_left_out":
        fields["full_rope"]["yarn"] = None                # plain rope at the full layers' theta
    elif name == "amplitude_left_out":
        ctx = _patched(transformer, "rope_amplitude", lambda _: lambda yarn: 1.0)
    elif name == "kinds_rope_swapped":
        ctx = _patched(transformer, "_apply_rope", _fault_kinds_rope_swapped)
    elif name == "renorm_left_out":
        fields["experts"]["norm_topk"] = False            # the chosen probabilities as they are
    elif name == "qk_norm_left_out":
        fields["qk_norm"] = False                         # its two scales a layer are handed over and never read
    elif name == "window_left_out":
        ctx = _patched(transformer, "cached_attention", serve_mixed._fault_window_left_out)
    elif name == "ring_one_page_short":
        ctx = _patched(paging.MixedKVPool, "ring_advance", serve_mixed._fault_ring_one_page_short)
    else:
        raise KeyError(name)
    with ctx:
        yield fields


FAULTS = ("yarn_left_out", "amplitude_left_out", "kinds_rope_swapped", "renorm_left_out", "qk_norm_left_out",
          "window_left_out", "ring_one_page_short")


def _run(seed=11, seconds=2.0, fault=None, **kw):
    manifest, entry, cell, config = common.load_cell(CELL)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds, trace=0, rehearse=True, keep_trace=False)
    with contextlib.ExitStack() as stack:
        if fault is not None:
            # ``serve_mixed.run`` enters ``planted`` itself: hand it this model's
            stack.enter_context(_patched(serve_mixed, "planted", lambda _: planted))
        return serve_mixed.run(args, manifest, entry, cell, config, time.time(), fault=fault, **kw)


def test_sound_program_is_correct_and_control_reads_wider():
    line = _run(control="fp8")
    compared = line["compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
    assert compared["control_logit_gap_mean"]["value"] > 100 * compared["served_logit_gap_mean"]["limit"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    line = _run(fault=fault)
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["served_logit_gap_mean"]["value"] > 100 * compared["served_logit_gap_mean"]["limit"]


def test_every_fault_is_planted_by_name():
    with pytest.raises(KeyError):
        with planted("no_such_fault", {"experts": {}}):
            pass
