"""Dense hybrid model step, the whole prefill chunk: the least time the
chip could take for the window's mean chunk
(`flops_granite.prefill_chunk_cost` at the program's own counts, prompt
tokens a chunk = `prefill_tokens` over `prefill_chunks`, and the cached
positions such a chunk's queries attend, from the prompts the benchmark
sent: a prompt of n tokens attends n (n + 1) / 2 in all) through
`flops.roofline_seconds`, over the median device time of the
prefill-chunk program. The chunk's share of the whole step's peak;
compute binds. Nothing where the program counts no chunks or the
configuration has no scan."""
from .. import flops, flops_granite, readers
from ._counters import delta

LAYER, SOURCE = "ssm_dense_model_step", "device_trace"


def attended_per_token(records):
    """Cached positions a prompt token attends (a layer), in the mean
    over the prompt tokens of `records`."""
    lens = [len(r.planned.prompt) for r in records or ()]
    total = sum(lens)
    return sum(n * (n + 1) / 2.0 for n in lens) / total if total else 0.0


def read(ctx):
    tr = ctx["trace"]
    prefill = readers.program(ctx, "prefill")
    if not tr or not prefill or "scan_chunk" not in ctx["shapes"]:
        return None
    chunk = readers.median(tr["module_s"].get(prefill, []))
    chunks = delta(ctx, "prefill_chunks")
    if not chunk or not chunks:
        return None
    tokens = delta(ctx, "prefill_tokens") / chunks
    ops, nbytes = flops_granite.prefill_chunk_cost(
        ctx["shapes"], tokens,
        tokens * attended_per_token(readers.records(ctx)))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, chunk)
