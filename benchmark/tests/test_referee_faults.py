"""The referee of the two dense serving cells, driven through their own
kinds at the rehearsal's sizes on the CPU (the look for a chip skipped,
the rest of a run as it is): a sound run is correct; the reference
computed in 8 bits and put in the program's place is not; and a run whose
decode program hands back another token than the one it chose, altered
where it is produced, is not."""
import argparse

import numpy as np
import pytest

from benchmark import harness
from benchmark.kinds import _serving
from benchmark.run import Context

CELLS = ("gpt2s-serve-batch", "mistral7b-serve-chat")
#: the rehearsal's sizes with a sample of many short answers (a greedy
#: answer of a toy model soon repeats itself, and a repeated token wins
#: by far: only the tokens next to the random prompt are close calls), and
#: this size's own limit: over 96 random positions of these toy widths a
#: bfloat16 forward lands 0.0-0.3 bfloat16 steps from the float32
#: reference's best and the 8-bit one 6-12 (CPU probes), where the cells'
#: limits belong to the published widths (PERF.md section 6)
TEST_SIZE = {"check": {"requests": 40, "min_tokens": 3, "max_tokens": 6,
                       "logit_tol_bf16_steps": 2}}


def _ctx(workload, seed):
    args = argparse.Namespace(seed=seed, seconds=3.0, trace=0,
                              rehearse=True, keep_trace=None)
    entry, cell, config = harness.load_cell(harness.load_benchmark(),
                                            workload, rehearse=True)
    cell = harness.overlay(cell, TEST_SIZE)
    ctx = Context(args, entry, cell, config, harness.CompileCounter())
    ctx.note = lambda what, **fields: None
    return ctx


class _NextToken:
    """The engine's decode program, handing back the token after the one
    it chose; whatever else the engine asks of its program is the
    program's."""

    def __init__(self, program, vocab):
        self.program, self.vocab = program, vocab

    def __call__(self, *args):
        tok, *rest = self.program(*args)
        return ((tok + 1) % self.vocab, *rest)

    def __getattr__(self, name):
        return getattr(self.program, name)


def _run(ctx, monkeypatch, alter_tokens=False):
    """One run of the cell's kind; (result, weights, records)."""
    kept = {}
    plain_referee, plain_build = _serving.referee, _serving.build_predictor

    def referee(ctx, weights, records, control=None):
        kept.update(weights=weights, records=records)
        return plain_referee(ctx, weights, records, control)

    def build_predictor(ctx, model):
        pred = plain_build(ctx, model)
        if alter_tokens:
            eng = pred.scheduler.engine
            eng._decode_wave = _NextToken(
                eng._decode_wave, harness.shapes(ctx.config)["vocab"])
        return pred

    monkeypatch.setattr(_serving, "referee", referee)
    monkeypatch.setattr(_serving, "build_predictor", build_predictor)
    result = harness.load_module("kinds", ctx.cell["kind"]).run(ctx)
    return result, kept["weights"], kept["records"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_the_8bit_control_is_not(workload,
                                                          monkeypatch):
    ctx = _ctx(workload, seed=2147484301)
    result, weights, records = _run(ctx, monkeypatch)
    gap, limit = result["checks"]["worst_logit_gap"]
    assert result["correct"] and result["failed"] == 0
    assert result["checks"]["tokens_checked_min"][0] >= 60
    assert 0 <= gap <= limit
    control = ctx.cell["check"]["control"]
    assert control == {"lower": "float8_e4m3fn"}
    c_gap, c_limit, c_same, c_n = _serving.referee(ctx, weights, records,
                                                   control)
    # the same limit, the same prompts and positions: the 8-bit forward's
    # own first choices lie past it
    assert c_n == result["checks"]["tokens_checked_min"][0]
    assert c_limit == pytest.approx(limit) and c_gap > c_limit > gap
    assert c_same < 1.0


@pytest.mark.parametrize("workload", CELLS)
def test_a_token_altered_where_it_is_produced_fails_the_run(workload,
                                                            monkeypatch):
    ctx = _ctx(workload, seed=2147484302)
    result, _, records = _run(ctx, monkeypatch, alter_tokens=True)
    gap, limit = result["checks"]["worst_logit_gap"]
    assert any(r.token_t for r in records)          # tokens were served
    assert not result["correct"] and gap > 3 * limit


def test_the_sample_holds_the_longest_request():
    import types

    def rec(n_prompt, n_out):
        return types.SimpleNamespace(
            request=types.SimpleNamespace(output_tokens=[0] * n_out),
            planned=types.SimpleNamespace(prompt=[0] * n_prompt))
    records = [rec(10, 9), rec(400, 12), rec(30, 2), rec(50, 40),
               rec(7, 8)] + [types.SimpleNamespace(request=None)]
    ctx = types.SimpleNamespace(seed=11, cell={"check": {
        "requests": 3, "min_tokens": 8}})
    picks = _serving.sampled(ctx, records)
    assert len(picks) == 3 and picks[0] is records[1]
    assert len({id(p) for p in picks}) == 3 and records[2] not in picks
    assert [id(p) for p in picks] == [id(p) for p in
                                      _serving.sampled(ctx, records)]
    ctx.cell["check"]["min_tokens"] = 99
    assert _serving.sampled(ctx, records) == []
