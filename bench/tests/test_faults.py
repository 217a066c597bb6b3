"""Drives the rest of a run (everything but the harness's look for a chip, at
the cells' tiny rehearsal sizes) with the timed path broken underneath, and
sees ``correct`` come out false - once for each fault a cell can have - and
true for the sound program and false for the lower-precision control."""

import argparse

import numpy as np
import pytest

from lib import common, serve, train

TRAIN = "gpt2-medium.train-seq1024"
SERVE = "gpt2-xl.serve-chat-surge"


def _run(kind, name, seed=11, seconds=1.5, trace=0, **kw):
    manifest, entry, cell, config = common.load_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace,
                              rehearse=True, keep_trace=False)
    import time

    return kind.run(args, manifest, entry, cell, config, time.time(), **kw)


def test_train_sound_program_is_correct():
    line = _run(train, TRAIN)
    assert line["correct"] is True
    assert line["attempted"] > 0 and "train_tokens_per_s" in line["metrics"]


def test_train_state_returned_unchanged_is_not_correct(monkeypatch):
    import accelerate_tpu as at

    original = at.Accelerator.compile_train_step

    def broken(self, loss_fn, **kw):
        real = original(self, loss_fn, **{**kw, "donate": False})

        def step(state, batch):
            _, metrics = real(state, batch)
            return state, metrics

        return step

    monkeypatch.setattr(at.Accelerator, "compile_train_step", broken)
    line = _run(train, TRAIN)
    assert line["correct"] is False
    assert line["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from accelerate_tpu.models import transformer

    original = transformer.lm_loss_fn

    def half(model):
        loss = original(model)

        def loss_fn(params, batch, rng=None):
            rows = batch["input_ids"]
            return loss(params, {"input_ids": rows[: rows.shape[0] // 2]}, rng)

        return loss_fn

    monkeypatch.setattr(transformer, "lm_loss_fn", half)
    line = _run(train, TRAIN)
    assert line["correct"] is False


def test_train_control_in_fp8_is_not_correct():
    """The control: the reference in the precision below the cell's, put in the
    program's place, against the reference."""
    manifest, entry, cell, config = common.load_cell(TRAIN)
    job, published, fields = train.sized(cell, config, True)
    from reference import gpt2

    rows = train.make_rows(5, job["rows"], job["seq_len"], published["vocab_size"])
    ref = train.reference_readings(gpt2, 5, published, rows, job)
    losses, grad, delta = train.reference_readings(gpt2, 5, published, rows, job, precision="fp8")
    compared, _ = train.compare({"losses": losses, "grad_norms": grad, "delta_norms": delta}, ref,
                                cell["rehearse"]["limits"])
    assert common.judge(compared) is False


def test_serve_sound_program_is_correct_and_control_reads_wider():
    line = _run(serve, SERVE, seconds=3, control="fp8")
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 5
    control = line["compared"]["control_logit_gap"]["value"]
    assert control > line["compared"]["served_logit_gap"]["limit"]


def test_serve_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from accelerate_tpu.serving import engine as engine_module

    original = engine_module.ServingEngine._emit

    def altered(self, toks, counts, *a, **kw):
        toks = np.array(toks)
        toks[:, 0] = (toks[:, 0] + 1) % self.config.vocab_size
        return original(self, toks, counts, *a, **kw)

    monkeypatch.setattr(engine_module.ServingEngine, "_emit", altered)
    line = _run(serve, SERVE, seconds=3)
    assert line["correct"] is False
    assert line["compared"]["served_logit_gap"]["value"] > line["compared"]["served_logit_gap"]["limit"]
