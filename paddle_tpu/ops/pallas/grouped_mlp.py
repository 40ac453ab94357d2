"""Grouped expert MLP over rows sorted by expert: one Pallas kernel, one
expert a grid step, in two forms of one body. Without a gate an expert
is `down(relu(up(x))^2)`, two matrices; with `gate=` it is
`down(silu(gate(x)) * up(x))`, three.

Rows [offsets[e], offsets[e + 1]) of `x` belong to expert e. A step
fetches that expert's matrices whole (each is read from memory once
a call, in one long transfer) and walks the expert's rows in blocks of
`ROWS`, straight out of the resident `x`; a block that straddles two
experts is visited by both, each keeping its own rows. Nothing is
padded to a capacity and no row is dropped: an expert with no rows costs
its fetch and no arithmetic.

An expert whose matrices, double-buffered, do not fit the chip's fast
memory beside the resident rows is walked in `tiles` slices of its
width (`_tiles`): a slice of `up` (and `gate`) gives a slice of the
hidden activation, which its slice of `down` adds to the rows' result,
so the grid is (experts, tiles) and every byte is still read once. At
one tile, which is every shape that fitted before, the kernel is the
one it was.

Every matrix is stored [experts, width, hidden] (`up` and `gate` as
[out, in], `down` as [in, out]): the device lays a matrix out in tiles
of 128 columns and would store [hidden, 1856] transposed; `hidden` tiles
exactly, so none is copied on the way into the kernel.

Why not `jax.lax.ragged_dot`: on a TPU it is a kernel tiled (256, 128,
128), about 40,000 grid steps a matmul at 128 experts x 2688 x 1856
whatever the rows (PERF.md, PR 27).
"""
import functools

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


def _kernel(x_ref, up_ref, *refs, gated, tiled):
    from jax.experimental import pallas as pl
    gate_ref = refs[0] if gated else None
    down_ref, offs_ref, o_ref = refs[-3:]
    e = pl.program_id(0)
    first_step = (e == 0) & (pl.program_id(1) == 0) if tiled else e == 0

    @pl.when(first_step)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    r0, r1 = offs_ref[e], offs_ref[e + 1]
    first = r0 // ROWS
    blocks = jnp.where(r1 > r0, (r1 + ROWS - 1) // ROWS - first, 0)

    def block(i, carry):
        start = pl.multiple_of((first + i) * ROWS, ROWS)
        xb = x_ref[pl.ds(start, ROWS), :]
        def into(w_ref):       # x @ w.T, w stored [width, hidden]
            return jax.lax.dot_general(
                xb, w_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        h = into(up_ref)
        h = (jax.nn.silu(into(gate_ref)) * h if gated
             else jnp.square(jnp.maximum(h, 0.0))).astype(xb.dtype)
        y = jnp.dot(h, down_ref[0], preferred_element_type=jnp.float32)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
        mine = (rows >= r0) & (rows < r1)
        was = o_ref[pl.ds(start, ROWS), :]
        # a slice of the width adds its part to what the slices before
        # it left (zeros at first); a whole expert's rows are its own
        o_ref[pl.ds(start, ROWS), :] = (
            was + jnp.where(mine, y, 0.0) if tiled
            else jnp.where(mine, y, was))
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)


@functools.lru_cache(maxsize=None)
def _call(rows, hidden, width, experts, dtype_name, interpret, gated=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    item = jnp.dtype(dtype_name).itemsize
    mats = 3 if gated else 2
    tiles = _tiles(rows, hidden, width, item, mats)
    tiled = tiles > 1
    grid = (experts, tiles) if tiled else (experts,)
    matrix = pl.BlockSpec((1, width // tiles, hidden),
                          lambda e, t=0: (e, t, 0))
    resident = pl.BlockSpec((rows, hidden), lambda *_: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, gated=gated, tiled=tiled), grid=grid,
        in_specs=[
            resident,
            *[matrix] * mats,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=resident,
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_vmem(rows, hidden, width // tiles, item,
                                   mats)),
        interpret=interpret, name="moe_experts")


def grouped_mlp(x, up, down, group_sizes, gate=None):
    """x [rows, hidden] sorted by expert, up and down (and gate, for the
    gated form) [experts, width, hidden], group_sizes [experts] int ->
    float32 [rows, hidden]."""
    rows, hidden = x.shape
    experts, width, _ = up.shape
    pad = -rows % ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    call = _call(rows + pad, hidden, width, experts, str(x.dtype),
                 jax.default_backend() != "tpu", gate is not None)
    mats = (up, down) if gate is None else (up, gate, down)
    # the scope, innermost at the call, names the instruction in a
    # device trace ("%moe_experts.1 = ... custom-call")
    with jax.named_scope("moe_experts"):
        out = call(x, *mats, offsets)
    return out[:rows]
