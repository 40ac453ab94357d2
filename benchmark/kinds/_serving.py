"""What the two serving kinds share: the predictor built through the
program's front door, the warm-up, the scheduler loop with the
benchmark's clock and spans around it, and the referee of served tokens.
"""
import time

import numpy as np

from .. import harness, loadgen


def build_predictor(ctx, model):
    """The cell's deployment through the program's front door."""
    from paddle_tpu import inference
    eng = ctx.cell["engine"]
    with ctx.phase("engine"):
        cfg = inference.Config().enable_llm_engine(
            num_slots=int(eng["num_slots"]), max_len=int(eng["max_len"]),
            prefill_len=int(eng["chunk"]), paged=True,
            block_size=int(eng["block_size"]))
        return inference.create_llm_predictor(cfg, model=model)


def build(ctx):
    """(model, weights, predictor) of the cell's deployment."""
    model, weights = harness.build_model(ctx.config, ctx.seed, ctx.phase)
    return model, weights, build_predictor(ctx, model)


def warm(ctx, pred, vocab):
    """Every shape the window will use, through the path it will use:
    a prompt of two chunks and one of a few tokens, decoded for a few
    waves. Both programs and the eager odds and ends around them compile
    (or load from the cache) here."""
    chunk = int(ctx.cell["engine"]["chunk"])
    rng = np.random.default_rng([ctx.seed, 0x3A83])
    with ctx.phase("warmup"):
        reqs = [pred.submit(prompt=rng.integers(0, vocab, n).tolist(),
                            max_tokens=3)
                for n in (chunk + 5, 7)]
        pred.run()
        bad = [r for r in reqs if len(r.output_tokens) != 3]
        if bad:
            raise harness.BenchmarkError(
                f"warm-up requests were not answered: {bad} "
                f"{[r.error for r in bad]}")


class Rounds:
    """Drives `scheduler.step()` and keeps the benchmark's own record of
    each round: (start, end, lanes decoding, cached positions they
    attend, requests still prefilling, blocks of the pool in use after
    it). The last is the program's own count (`health()`, what /healthz
    serves), read between rounds."""

    def __init__(self, ctx, pred, live_records):
        self.ctx, self.sched, self.pred = ctx, pred.scheduler, pred
        self.live_records = live_records      # callable -> records in flight
        self.log = []

    def one(self):
        dec = att = pre = 0
        for r in self.live_records():
            if r.done:
                continue
            if r.token_t:
                dec += 1
                att += len(r.planned.prompt) + len(r.token_t)
            elif r.request is not None and r.request.prefill_time:
                pre += 1
        t0 = time.perf_counter()
        with self.ctx.span("bench/step"):
            pending = self.sched.step()
        t1 = time.perf_counter()
        self.log.append((t0, t1, dec, att, pre,
                         self.pred.health().get("cache_blocks_used")))
        if not pending:
            with self.ctx.span("bench/idle_wait"):
                time.sleep(0.002)
        return pending


def measure(ctx, turn):
    """The measured window: `turn()` (one scheduler round and whatever
    the loop does after it) until `--seconds` have passed, closing on a
    round's end. A traced run profiles `trace_seconds` of it from 0.4 of
    the way in. Returns (start, end)."""
    t0 = ctx.open_window()
    traced = not ctx.trace
    while time.perf_counter() - t0 < ctx.seconds:
        if not traced and time.perf_counter() - t0 >= 0.4 * ctx.seconds:
            traced = True
            with ctx.traced_window():
                t_stop = time.perf_counter() + float(
                    ctx.cell.get("trace_seconds", 5.0))
                while time.perf_counter() < t_stop:
                    turn()
            continue
        turn()
    return t0, ctx.close_window()


def snapshot(pred):
    """The program's own counters, as the scheduler sums them: every
    number of its snapshot and the two groups the readers and the verdict
    take (`phase_seconds`, `faults`). A counter a later program adds
    reaches its reader with no edit here."""
    snap = pred.metrics.snapshot()
    out = {k: v for k, v in snap.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
    out.update(phase_seconds=dict(snap["phase_seconds"]),
               faults=dict(snap["faults"]))
    return out


def sampled(ctx, records):
    """The requests the referee reads: of those that were served
    `check.min_tokens` tokens or more, the longest (prompt and served
    tokens) and `check.requests` - 1 others drawn from the seed."""
    check = ctx.cell["check"]
    cand = [r for r in records if r.request is not None
            and len(r.request.output_tokens) >= int(check["min_tokens"])]
    if not cand:
        return []
    longest = max(range(len(cand)), key=lambda i: (
        len(cand[i].planned.prompt) + len(cand[i].request.output_tokens), -i))
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    order = [longest] + [i for i in rng.permutation(len(cand))
                         if i != longest]
    return [cand[i] for i in order[:int(check["requests"])]]


def referee(ctx, weights, records, control=None):
    """Served tokens against the float32 reference.

    With weights from a seed the largest logit wins by little, and
    bfloat16 rounding flips it now and then, so equal tokens cannot be
    asked for. Asked instead: at every sampled position the token that
    was served lies within `tol` of the reference's best logit there,
    given the tokens served before it. tol is `logit_tol_bf16_steps`
    bfloat16 steps (2^-8) at the size of the largest reference logit: a
    bfloat16 forward through a dozen layers lands a few steps from the
    float32 one (the cell's file gives the steps measured on the chip), an
    8-bit one tens of steps away.

    With `control` (keywords of the reference's control forward, the
    cell's `check.control`) the tokens judged are not the served ones but
    those that forward puts first at the same positions of the same
    prompts and served histories: the reference in a lower precision in
    the program's place. The limit has to call it wrong.

    Returns (worst gap, tol, share of sampled tokens equal to the
    reference's argmax, tokens sampled).
    """
    check = ctx.cell["check"]
    ref = harness.reference_for(ctx.config)
    sh = harness.shapes(ctx.config)
    rw = ref.from_state_dict(weights, sh["layers"])
    pad = int(check["pad_to"])
    worst = tol = 0.0
    same = total = 0
    for r in sampled(ctx, records):
        out = list(r.request.output_tokens)[:int(check["max_tokens"])]
        prompt = r.planned.prompt
        n = len(prompt)
        ctx_ids = np.zeros((1, pad), np.int32)
        ctx_ids[0, :n + len(out) - 1] = prompt + out[:-1]
        # a fixed number of rows (the last one repeated), so that the
        # reference compiles once for every request
        rows = np.minimum(np.arange(n - 1, n - 1 + int(check["max_tokens"])),
                          n - 2 + len(out))
        lo = np.asarray(ref.forward(rw, ctx_ids, ctx.config,
                                    rows=rows)[0])[:len(out)]
        if not np.isfinite(lo).all():
            return float("inf"), 0.0, 0.0, 0
        if control:
            out = np.asarray(ref.forward(rw, ctx_ids, ctx.config, rows=rows,
                                         **control)[0])[:len(out)].argmax(1)
        gaps = lo.max(axis=1) - lo[np.arange(len(out)), out]
        worst = max(worst, float(gaps.max()))
        tol = max(tol, float(check["logit_tol_bf16_steps"]) * 2.0 ** -8
                  * float(np.abs(lo).max()))
        same += int((lo.argmax(axis=1) == np.asarray(out)).sum())
        total += len(out)
    return worst, tol, (same / total if total else 0.0), total


def finish(ctx, pred, weights, records, rounds, window, snap0, snap1,
           attempted, failed, extra_obs):
    """Close the predictor, judge, and hand the observations on."""
    import jax
    mem = harness.memory_peak_bytes(jax.devices()[:1])
    pred.close(drain=False)
    with ctx.phase("check"):
        worst, tol, same, n = referee(ctx, weights, records)
        # a traced run also notes what the limit makes of the reference in
        # a lower precision put in the program's place: not correct
        if ctx.trace and ctx.cell["check"].get("control"):
            c_worst, c_tol, c_same, c_n = referee(
                ctx, weights, records, ctx.cell["check"]["control"])
            ctx.note("control", forward=ctx.cell["check"]["control"],
                     worst_logit_gap=c_worst, tol=c_tol, argmax_match=c_same,
                     tokens_checked=c_n, correct=bool(c_worst <= c_tol))
    faults = {k: snap1["faults"].get(k, 0) - snap0["faults"].get(k, 0)
              for k in snap1["faults"]}
    faults = {k: v for k, v in faults.items() if v}
    rejected = snap1["rejected"] - snap0["rejected"]
    used = sorted(r[5] for r in rounds.log
                  if r[0] >= window[0] and r[1] <= window[1]
                  and r[5] is not None)
    ctx.note("check", worst_logit_gap=worst, tol=tol, argmax_match=same,
             tokens_checked=n, faults=faults, rejected=rejected,
             pool_blocks_used_p50=used[len(used) // 2] if used else None)
    obs = {"window": window, "records": records, "rounds": rounds.log,
           "snap0": snap0, "snap1": snap1,
           "engine": dict(ctx.cell["engine"])}
    obs.update(extra_obs)
    return {
        "attempted": attempted, "failed": failed,
        "correct": (n > 0 and worst <= tol and not faults
                    and rejected == 0),
        "checks": {"worst_logit_gap": [worst, tol],
                   "tokens_checked_min": [n, 1],
                   "faults": [sum(faults.values()), 0],
                   "rejected": [rejected, 0]},
        "memory_peak_bytes": mem, "obs": obs,
    }
