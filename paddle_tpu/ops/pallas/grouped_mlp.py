"""Grouped expert MLP over rows sorted by expert: one Pallas kernel that
walks the experts one at a time, in two forms of one body. Without a gate
an expert is `down(relu(up(x))^2)`, two matrices; with `gate=` it is
`down(silu(gate(x)) * up(x))`, three.

Rows [offsets[e], offsets[e + 1]) of `x` belong to expert e. A call
walks the experts that hold rows and no other (`walk`: those experts in
ascending order, and how many), so an expert no row chose is neither
fetched nor stepped; where every expert holds rows the walk is every
expert in order. The kernel copies each walked expert's matrices by
hand from the stacks in HBM (each read once a call, in one long
transfer) into one of two buffers, the next expert's while this one
computes, and walks the expert's rows in blocks of `ROWS`, straight out
of the resident `x`; a block that straddles two experts is visited by
both, each keeping its own rows. Nothing is padded to a capacity and no
row is dropped.

The rows are the call's first operand and the walk, the steps and the
offsets its last, in SMEM: a Mosaic call whose first operand is an s32
array is what a device trace's reader takes for the paged attention
kernel (`benchmark/trace_reduce.py::kernel_class`), and scalar-prefetch
operands, like a traced grid bound, come first.

A program that wants to know what its calls fetched traces them inside
`fetch_tally()`: each routed layer notes the count its calls walked
(`note_fetched`), and the program returns their sum beside its result.

An expert whose matrices, double-buffered, do not fit the chip's fast
memory beside the resident rows is walked in `tiles` slices of its
width (`_tiles`): a slice of `up` (and `gate`) gives a slice of the
hidden activation, which its slice of `down` adds to the rows' result,
so a call takes (walked experts x tiles) steps and every byte is still
read once.

Every matrix is stored [experts, width, hidden] (`up` and `gate` as
[out, in], `down` as [in, out]): the device lays a matrix out in tiles
of 128 columns and would store [hidden, 1856] transposed; `hidden` tiles
exactly, so none is copied on the way into the kernel.

Why not `jax.lax.ragged_dot`: on a TPU it is a kernel tiled (256, 128,
128), about 40,000 grid steps a matmul at 128 experts x 2688 x 1856
whatever the rows (PERF.md, PR 27).
"""
import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

ROWS = 16       # rows a block: one packed bfloat16 tile of sublanes
#: what one call may plan of a v5e's 128 MiB of VMEM
VMEM_BUDGET = 120 << 20


def _vmem(rows, hidden, width, item, mats):
    """The matrices, double-buffered, beside the resident rows and
    result, and a margin."""
    return (2 * mats * width * hidden * item
            + 2 * rows * hidden * (item + 4) + (8 << 20))


def _tiles(rows, hidden, width, item, mats):
    """Slices of an expert's width a call walks: the fewest (a power of
    two, each slice whole 128-column tiles) whose plan fits the budget."""
    tiles = 1
    while (_vmem(rows, hidden, width // tiles, item, mats) > VMEM_BUDGET
           and width % (256 * tiles) == 0):
        tiles *= 2
    return tiles


def max_rows(hidden, width, item, mats):
    """The most rows, a multiple of `ROWS`, one call plans inside the
    budget: resident beside an expert cut in as many slices as `_tiles`
    may cut it (at least one block of rows)."""
    tiles = 1
    while width % (256 * tiles) == 0:
        tiles *= 2
    free = VMEM_BUDGET - _vmem(0, hidden, width // tiles, item, mats)
    return max(ROWS, free // (2 * hidden * (item + 4)) // ROWS * ROWS)


def walk(group_sizes):
    """(walk int32[experts], n): the experts a call steps through, those
    that hold rows first in ascending order and then the rest, and the
    steps it runs: the experts that hold rows, at least one (the kernel
    starts its first step's copy before it reads the count)."""
    held = group_sizes > 0
    order = jnp.argsort(jnp.logical_not(held), stable=True)
    return (order.astype(jnp.int32),
            jnp.maximum(jnp.sum(held, dtype=jnp.int32), 1))


_TALLIES = threading.local()


@contextlib.contextmanager
def fetch_tally():
    """Collect, while a program is traced in this scope, the experts its
    kernel calls fetch: the list it yields gets one traced int32 a
    routed layer (`note_fetched`). Outside such a scope nothing is noted
    and a program is what it was."""
    stack = _TALLIES.__dict__.setdefault("stack", [])
    stack.append([])
    try:
        yield stack[-1]
    finally:
        stack.pop()


def note_fetched(n):
    """Add `n`, the experts a layer's calls fetched, to the innermost
    `fetch_tally()` open in this thread, if any."""
    stack = getattr(_TALLIES, "stack", None)
    if stack:
        stack[-1].append(n)


def _kernel(rows_hbm, *refs, gated, tiles):
    """One call: the walked experts' slices in order, each copied by hand
    from the stacks in HBM into one of two buffers (the next slice's
    copy runs while this one computes), then the expert's rows in blocks
    of `ROWS`. The rows arrive beside the first slice."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    mats = 3 if gated else 2
    stacks = refs[:mats]
    walk_ref, steps_ref, offs_ref, o_ref, x_ref = refs[mats:mats + 5]
    bufs, (sem_ref, rows_sem) = refs[mats + 5:-2], refs[-2:]
    part = bufs[0].shape[1]              # width // tiles
    total = steps_ref[0] * tiles

    def copies(s, slot):
        e, t = walk_ref[s // tiles], s % tiles
        return [pltpu.make_async_copy(stack.at[e, pl.ds(t * part, part)],
                                      buf.at[slot], sem_ref.at[slot, j])
                for j, (stack, buf) in enumerate(zip(stacks, bufs))]

    rows_in = pltpu.make_async_copy(rows_hbm, x_ref, rows_sem)
    rows_in.start()
    for copy in copies(0, 0):
        copy.start()
    o_ref[...] = jnp.zeros_like(o_ref)     # while the first copies run
    rows_in.wait()

    def step(s, carry):
        slot = s % 2

        @pl.when(s + 1 < total)
        def _():
            for copy in copies(s + 1, 1 - slot):
                copy.start()

        for copy in copies(s, slot):
            copy.wait()
        up, down = bufs[0].at[slot], bufs[-1].at[slot]
        gate = bufs[1].at[slot] if gated else None
        e = walk_ref[s // tiles]
        r0, r1 = offs_ref[e], offs_ref[e + 1]
        first = r0 // ROWS

        def block(i, carry):
            start = pl.multiple_of((first + i) * ROWS, ROWS)
            xb = x_ref[pl.ds(start, ROWS), :]

            def into(w_ref):       # x @ w.T, w stored [width, hidden]
                return jax.lax.dot_general(
                    xb, w_ref[...], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

            h = into(up)
            h = (jax.nn.silu(into(gate)) * h if gated
                 else jnp.square(jnp.maximum(h, 0.0))).astype(xb.dtype)
            y = jnp.dot(h, down[...], preferred_element_type=jnp.float32)
            rows = start + jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
            mine = (rows >= r0) & (rows < r1)
            was = o_ref[pl.ds(start, ROWS), :]
            # a slice of the width adds its part to what the slices before
            # it left (zeros at first); a whole expert's rows are its own
            o_ref[pl.ds(start, ROWS), :] = (
                was + jnp.where(mine, y, 0.0) if tiles > 1
                else jnp.where(mine, y, was))
            return carry

        blocks = jnp.where(r1 > r0, (r1 + ROWS - 1) // ROWS - first, 0)
        jax.lax.fori_loop(0, blocks, block, 0)
        return carry

    jax.lax.fori_loop(0, total, step, 0)


@functools.lru_cache(maxsize=None)
def _call(rows, hidden, width, dtype_name, interpret, gated=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    item = jnp.dtype(dtype_name).itemsize
    mats = 3 if gated else 2
    tiles = _tiles(rows, hidden, width, item, mats)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, gated=gated, tiles=tiles),
        # the rows first, the walk last (the module's docstring says why)
        in_specs=[*[pl.BlockSpec(memory_space=pl.ANY)] * (1 + mats),
                  smem, smem, smem],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rows, hidden), jnp.dtype(dtype_name)),
            *[pltpu.VMEM((2, width // tiles, hidden), jnp.dtype(dtype_name))
              ] * mats,
            pltpu.SemaphoreType.DMA((2, mats)),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem(rows, hidden, width // tiles, item,
                                   mats)),
        interpret=interpret, name="moe_experts")


def grouped_mlp(x, up, down, group_sizes, gate=None):
    """x [rows, hidden] sorted by expert, up and down (and gate, for the
    gated form) [experts, width, hidden], group_sizes [experts] int ->
    float32 [rows, hidden]."""
    rows, hidden = x.shape
    width = up.shape[1]
    pad = -rows % ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    order, steps = walk(group_sizes)
    call = _call(rows + pad, hidden, width, str(x.dtype),
                 jax.default_backend() != "tpu", gate is not None)
    mats = (up, down) if gate is None else (up, gate, down)
    # the scope, innermost at the call, names the instruction in a
    # device trace ("%moe_experts.1 = ... custom-call")
    with jax.named_scope("moe_experts"):
        out = call(x, *mats, order, steps[None], offsets)
    return out[:rows]
