"""Ask the TPU's compiler, without a TPU: the Pallas kernels of the main
paths are compiled at real widths for one described v5e chip
(`jax.experimental.topologies`), so a kernel that only ever ran in
interpret mode cannot reach the chip unseen. Nothing runs — a pass says
the compiler accepts the kernel, not that its results are right.

The topology is described inside the module-scoped fixture below and
nowhere else: only one process may hold libtpu, so it must not load
while a module is imported (every xdist worker imports every test file)
and these tests must stay in this one file. Compiles run in the test's
own process, with the persistent cache off (a described-chip executable
is written to the cache but cannot be read back without a chip).

The kernels choose `interpret=` from `jax.default_backend()`, which is
"cpu" here; the tests steer that with monkeypatch, not the program.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.nn import paged_attention as pa
from paddle_tpu.ops.pallas.flash_attention import _flash_array


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 host, with the persistent cache off for as
    long as this file's tests compile for it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a test of another file, run earlier by the same xdist worker, may
    # have left its mesh of CPU devices installed: the flash wrapper
    # would then shard over it instead of compiling for the one chip
    monkeypatch.setattr(mesh_mod, "_current_mesh", None)


def _kernels_in(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text().count(
        "tpu_custom_call")


def _kernel_names(fn, *shapes):
    """Names of the compiled program's Mosaic instructions, without the
    ".<n>" suffix: what a device trace calls their events."""
    txt = jax.jit(fn).lower(*shapes).compile().as_text()
    return sorted(re.match(r"\s*(?:ROOT )?%([^ ]+?)(\.\d+)? = ", line).group(1)
                  for line in txt.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line)


def _paged_args(sharding, b, h, hkv, c, d, bs=16, nblk=64):
    """(q, pool, tables, positions) of a paged engine at block 16, 64
    blocks per lane (max_len 1024), a bf16 pool in its stored form."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (sds((b, h, c, d), jnp.bfloat16),
            sds((b * nblk + 1, hkv, bs, 2 * d), jnp.bfloat16),
            sds((b, nblk), jnp.int32), sds((b,), jnp.int32))


@pytest.mark.parametrize("name,layout,b,s,h,d,window", [
    ("gpt2s-bshd", "bshd", 8, 1024, 12, 64, None),
    ("gpt2s-bhsd", "bhsd", 8, 1024, 12, 64, None),
    ("gpt2m-bshd", "bshd", 4, 1024, 16, 64, None),
    ("8k-window1024", "bshd", 1, 8192, 12, 64, 1024),
])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, as_on_tpu, name, layout,
                                        b, s, h, d, window):
    shape = (b, s, h, d) if layout == "bshd" else (b, h, s, d)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return _flash_array(q, k, v, causal=True, layout=layout,
                            window=window).astype(jnp.float32).sum()

    # forward, dq and dk/dv: three kernels
    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 3


def test_flash_under_a_dp2_mp2_mesh_compiles_for_v5e(topo, as_on_tpu,
                                                     monkeypatch):
    """ShardedTrainStep traces the model for the installed mesh, all
    four chips. The TPU compiler cannot partition a Mosaic kernel, so
    the flash wrapper has to call it per shard (batch over dp, heads
    over mp); virtual CPU devices never see this, interpret mode being
    plain HLO."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "mp"))
    monkeypatch.setattr(mesh_mod, "_current_mesh", mesh)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

    def loss(q, k, v):
        return _flash_array(q, k, v, causal=True, layout="bshd").astype(
            jnp.float32).sum()

    txt = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert txt.count("tpu_custom_call") == 3
    # each chip runs the kernels on its own [4, 1024, 6, 64] shard:
    # nothing is gathered to feed them
    assert "all-gather" not in txt


@pytest.mark.parametrize("name,b,h,hkv,c,d,window", [
    ("gpt2s-decode", 8, 12, 12, 1, 64, None),
    ("gpt2s-chunk128", 1, 12, 12, 128, 64, None),
    ("gqa32x8-d128-decode", 8, 32, 8, 1, 128, None),
    ("gqa32x8-d128-chunk128", 1, 32, 8, 128, 128, None),
    ("gqa32x8-d128-decode-window256", 8, 32, 8, 1, 128, 256),
])
def test_paged_core_server_resolves_on_tpu_compiles_for_v5e(
        one_chip, as_on_tpu, name, b, h, hkv, c, d, window):
    """The engine's decode wave (C == 1) and prefill chunk (C == 128)."""
    assert pa.resolve_kernel() == "pallas"

    def fn(q, pool, tables, pos):
        return pa.attend(q, pool, tables, pos, d ** -0.5, window=window)

    assert _kernels_in(fn, *_paged_args(one_chip, b, h, hkv, c, d)) == 1


@pytest.mark.parametrize("name,b,h,hkv,c,d,nblk,window", [
    # gpt2s-serve-batch: 128 lanes, max_len 1024
    ("gpt2s-batch-wave", 128, 12, 12, 1, 64, 64, None),
    ("gpt2s-batch-chunk128", 1, 12, 12, 128, 64, 64, None),
    # mistral7b-serve-chat: 64 lanes, max_len 2560, window 4096
    ("mistral-chat-wave", 64, 32, 8, 1, 128, 160, 4096),
    ("mistral-chat-chunk128", 1, 32, 8, 128, 128, 160, 4096),
    # a window inside the table, and the speculative verify wave's form
    # (C = k + 1 queries at per-lane starts over every lane)
    # nemotron3n-serve-reason: 128 lanes, max_len 2048, GQA 32/2
    ("nemotron-reason-wave", 128, 32, 2, 1, 128, 128, None),
    ("nemotron-reason-chunk128", 1, 32, 2, 128, 128, 128, None),
    ("mistral-wave-window1024", 64, 32, 8, 1, 128, 160, 1024),
    ("gpt2s-verify-k4", 128, 12, 12, 5, 64, 64, None),
    # granite4hm-serve-longdoc: 32 lanes, max_len 17408, GQA 32/8 x 64;
    # its chunk is 512 queries x 4 a kv-head over 1,088 pages
    ("granite-longdoc-wave", 32, 32, 8, 1, 64, 1088, None),
    ("granite-longdoc-chunk512", 1, 32, 8, 512, 64, 1088, None),
    # gpt2s-serve-batch at its 256 lanes
    ("gpt2s-batch-wave-256", 256, 12, 12, 1, 64, 64, None),
    # a table shorter than the pages a step would take
    ("short-table-wave", 8, 32, 8, 1, 128, 8, None),
    ("short-table-chunk", 1, 32, 8, 128, 128, 8, None),
    # laguna-xs2-serve-agent-mix: 16 lanes, max_len 32,768 (tables of
    # 2,048 pages), 8 kv-heads of 128; full layers 48 heads (6 a
    # kv-head), window layers 64 (8 a kv-head) banded to 512 keys
    ("laguna-full-wave", 16, 48, 8, 1, 128, 2048, None),
    ("laguna-full-chunk512", 1, 48, 8, 512, 128, 2048, None),
    ("laguna-window-wave", 16, 64, 8, 1, 128, 2048, 512),
    ("laguna-window-chunk512", 1, 64, 8, 512, 128, 2048, 512),
])
def test_paged_core_compiles_at_the_cells_shapes_for_v5e(
        one_chip, as_on_tpu, name, b, h, hkv, c, d, nblk, window):
    """The serving cells' real shapes: one kernel, named
    `paged_attention`, per attention call, whatever tile the shapes
    choose (`_tile`: every kv-head and up to 32 pages a step in a wave,
    one kv-head's whole chunk against up to 32 pages in a chunk)."""
    def fn(q, pool, tables, pos):
        return pa.attend(q, pool, tables, pos, d ** -0.5, window=window,
                         kernel="pallas")

    assert _kernel_names(fn, *_paged_args(one_chip, b, h, hkv, c, d,
                                          nblk=nblk)) == ["paged_attention"]


_HLO_TYPES = {"bfloat16": "bf16", "float32": "f32"}


def _pool_stays(compiled, pools):
    """What has to hold of a compiled serving program for the pool to
    pass through it in place (PERF.md, PR 28): no instruction whose
    result is pool-sized is a `copy` or a `transpose`, every pool array
    is aliased from parameter to result, and nothing is padded (the
    arguments are the pools' logical bytes, within 2%; the temporaries
    hold no second pool)."""
    txt = compiled.as_text()
    logical = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    for shape, dtype in {(p.shape, str(p.dtype)) for p in pools}:
        dims = ",".join(map(str, shape))
        moved = [line.strip()[:120] for line in txt.splitlines()
                 if re.search(r"= %s\[%s\]\S* (copy|transpose)\("
                              % (_HLO_TYPES[dtype], dims), line)]
        assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= logical, (mem.alias_size_in_bytes,
                                                logical)
    least = min(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert mem.temp_size_in_bytes < least / 2, mem.temp_size_in_bytes
    return mem.argument_size_in_bytes / logical


@pytest.mark.parametrize("name,blocks,lanes,h,hkv,d,nblk,c,window", [
    # (blocks, lanes) as the cells reserve them: slots x max_len / 16 + 1
    ("gpt2s-batch-wave", 8193, 128, 12, 12, 64, 64, 1, None),
    ("gpt2s-batch-chunk128", 8193, 128, 12, 12, 64, 64, 128, None),
    ("mistral-chat-wave", 10241, 64, 32, 8, 128, 160, 1, 4096),
    ("mistral-chat-chunk128", 10241, 64, 32, 8, 128, 160, 128, 4096),
    ("nemotron-reason-wave", 16385, 128, 32, 2, 128, 128, 1, None),
    ("nemotron-reason-chunk128", 16385, 128, 32, 2, 128, 128, 128, None),
    ("granite-longdoc-wave", 34817, 32, 32, 8, 64, 1088, 1, None),
    ("granite-longdoc-chunk512", 34817, 32, 32, 8, 64, 1088, 512, None),
])
def test_pool_passes_through_write_and_attention_in_place_for_v5e(
        one_chip, as_on_tpu, name, blocks, lanes, h, hkv, d, nblk, c,
        window):
    """One layer of a serving program at a cell's real pool: the call a
    model's attention makes (the K/V write, then the paged kernel), the
    pool donated. The pool is neither copied nor padded on the way
    (head_dim 64 as 128), and the kernel is the one named
    `paged_attention`."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b = lanes if c == 1 else 1

    def layer(pool, q, k, v, tables, start, valid_len):
        out, pool = pa.paged_attend(q, k, v, pool, tables, start, valid_len,
                                    d ** -0.5, window=window,
                                    kernel="pallas")
        return pool, out

    pool = sds((blocks, hkv, 16, 2 * d), jnp.bfloat16)
    kv = sds((b, hkv, c, d), jnp.bfloat16)
    args = (pool, sds((b, h, c, d), jnp.bfloat16), kv, kv,
            sds((b, nblk), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32))
    assert _kernel_names(layer, *args) == ["paged_attention"]
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(*args).compile()
    assert _pool_stays(compiled, [pool]) < 1.02


@pytest.mark.parametrize("name,lanes,c,nblk,blocks,scale,kernels", [
    # kanana2-serve-doc: 32 slots x 10240 positions / 16 + 1 pages of
    # 16 rows x 640 (576 stored in whole vregs), 32 heads, MLA 512 + 64
    ("kanana-doc-wave", 32, 1, 640, 20481, 192 ** -0.5,
     ["paged_latent_attention"]),
    ("kanana-doc-chunk512", 1, 512, 640, 20481, 192 ** -0.5, []),
    # xing4-serve-repo-reason: 16 slots x 24,576 positions / 16 + 1
    # pages, tables of 1,536 pages, the YaRN-corrected softmax scale
    ("xing-reason-wave", 16, 1, 1536, 24577, 0.1447,
     ["paged_latent_attention"]),
    ("xing-reason-chunk512", 1, 512, 1536, 24577, 0.1447, []),
])
def test_latent_pool_passes_through_write_and_attention_in_place_for_v5e(
        one_chip, as_on_tpu, name, lanes, c, nblk, blocks, scale, kernels):
    """One latent-attention layer of a serving program at the cell's
    real pool: the call the model's attention makes (the rows' write,
    then the absorbed kernel in a wave, the expanded loop in a chunk),
    the pool donated: neither copied nor padded on the way."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, rank, rope, nope, v = 32, 512, 64, 128, 128
    assert pa.latent_path(c, rank, rope, nope, v) == \
        ("absorbed" if kernels else "expanded")

    def layer(pool, q_nope, q_rope, rows, w, tables, start, valid_len):
        out, pool = pa.paged_attend_latent(
            q_nope, q_rope, rows, w, pool, tables, start, valid_len,
            scale, kernel="pallas")
        return pool, out

    pool = sds((blocks, 16, pa.latent_width(rank, rope)), jnp.bfloat16)
    args = (pool, sds((lanes, heads, c, nope), jnp.bfloat16),
            sds((lanes, heads, c, rope), jnp.bfloat16),
            sds((lanes, c, rank + rope), jnp.bfloat16),
            sds((rank, heads, nope + v), jnp.bfloat16),
            sds((lanes, nblk), jnp.int32), sds((lanes,), jnp.int32),
            sds((lanes,), jnp.int32))
    assert _kernel_names(layer, *args) == kernels
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(*args).compile()
    # the other arguments (W_kv_b is 8.4 MB, a chunk's queries 6) are 2
    # to 3.5% of the pool's 419 MB (503 MB): nothing is padded
    assert _pool_stays(compiled, [pool]) < 1.04


def _latent_query_untransposed(text):
    """The absorbed kernel's queries come from the absorbed product and
    its output goes to W_UV with no transpose and no copy on either side:
    the instruction that makes the kernel's [H * B * C, W] query operand
    and every one that reads its output is (through bitcasts) neither a
    copy nor a transpose, nor a fusion with one in its body."""
    lines = text.splitlines()
    defs = {m.group(1): line for line in lines
            for m in [re.match(r"\s*(?:ROOT )?%(\S+) = ", line)] if m}

    def plain(name):
        line = defs[name]
        if re.search(r"= \S+ (copy|transpose|copy-start)\(", line):
            return False
        through = re.search(r"= \S+ bitcast\(%([\w.\-]+)\)", line)
        if through:
            return plain(through.group(1))
        called = re.search(r"calls=%([\w.\-]+)", line)
        if called is None:
            return True
        body = re.search(r"^%" + re.escape(called.group(1))
                         + r" .*?^}", text, re.M | re.S).group(0)
        return " transpose(" not in body and " copy(" not in body

    kernels = [line for line in lines if "paged_latent_attention" in line
               and 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    for line in kernels:
        name = re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
        queries = [o for o in re.findall(r"%([\w.\-]+)",
                                         line.split("custom-call(")[1])
                   if defs[o].split("=")[1].strip().startswith("f32[")]
        assert queries and all(plain(o) for o in queries), queries
        readers = [m.group(1) for other in lines
                   if re.search(r"%" + re.escape(name) + r"[,)]", other)
                   for m in [re.match(r"\s*(?:ROOT )?%(\S+) = ", other)]
                   if m and m.group(1) != name]
        assert readers and all(plain(r) for r in readers), readers


def _engine_program_args(eng, program):
    """(the engine's own closure, the arguments a round stages for it)
    of a paged engine's decode wave or prefill chunk."""
    slots, chunk = eng.num_slots, eng.prefill_chunk_len
    if program == "decode_wave":
        return eng._decode_wave_fn, eng._wave_args(
            [True] * slots, np.zeros(slots, bool), jax.random.PRNGKey(0))
    # the tuple `prefill_step` stages: the lane state, the packed chunk,
    # the resident zero bias row, the engine's key
    greedy = eng._sampling_state(False, 1.0, 0, 1.0, None, False)
    return eng._prefill_fn, (
        *eng._prefill_chunk_args(0),
        *eng._prompt_args(0, np.zeros(chunk, np.int32), 0, chunk, 0,
                          greedy, eng._slot_table(0)))


def _compile_engine_program(eng, program, sharding):
    """A paged engine's decode wave or prefill chunk as the engine builds
    it (its own closure, the arguments a round stages, the caches and
    the lane state donated), compiled for the described chip."""
    fn, args = _engine_program_args(eng, program)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), args)
    return jax.jit(fn, donate_argnums=eng._program_donate_argnums
                   ).lower(*shapes).compile()


def _lane_state_stays(compiled, eng, program, carried):
    """The lanes' tokens and positions go through a serving program as
    the pools do: two int32[slots] device arrays the engine holds,
    donated, each aliased from parameter to result beside the `carried`
    arrays (the header's alias list holds them all and the aliased bytes
    cover them), so the host is not on a token's way from one program to
    the next; and the program takes ONE argument from the host, the
    packed numpy array, as since PR 32."""
    _, args = _engine_program_args(eng, program)
    assert args[3] is eng._lane_tok and args[4] is eng._lane_pos
    assert eng._program_donate_argnums == (2, 3, 4)
    for lane in args[3:5]:
        assert isinstance(lane, jax.Array)
        assert (lane.shape, lane.dtype) == ((eng.num_slots,), jnp.int32)
    leaves = jax.tree_util.tree_leaves(args)
    assert [type(a) for a in leaves].count(np.ndarray) == 1
    assert all(isinstance(a, (np.ndarray, jax.Array)) for a in leaves)
    header = compiled.as_text().split("\n", 1)[0]
    aliases = re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(carried) + 2, header[:400]
    logical = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in carried)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        logical + 2 * 4 * eng.num_slots


@pytest.mark.parametrize("program", ["decode_wave", "prefill_chunk"])
@pytest.mark.parametrize("family", ["gpt-mha-d64", "llama-gqa-d128",
                                    "deepseek-mla", "deepseek-mla-mhc"])
def test_engine_programs_keep_the_pool_in_place_for_v5e(one_chip, as_on_tpu,
                                                        family, program):
    """The same, of the programs as the engine builds them (its own
    closures, its own arguments, the caches donated): a small GPT with
    heads of 64 and a small GQA Llama with heads of 128."""
    import paddle_tpu as pt
    from paddle_tpu.nlp import (DeepseekV3Config, DeepseekV3ForCausalLM,
                                GPTConfig, GPTForPretraining, LlamaConfig,
                                LlamaForCausalLM)
    from paddle_tpu.serving import PagedServingEngine

    pt.seed(0)
    # 2,049 pages: a pool of 17 MB and more, which the compiler cannot
    # decide to hold in VMEM whole, as it cannot hold any cell's
    slots, chunk, max_len, blocks = 16, 128, 512, 2049
    if family == "deepseek-mla":
        # kanana2-serve-doc's programs: its widths, heads, lanes, table
        # and chunk; two layers (one dense, one of 8 experts), a small
        # vocabulary and a tenth of the pages, so that the sandbox holds
        # the model
        model = DeepseekV3ForCausalLM(DeepseekV3Config(
            vocab_size=512, num_hidden_layers=2, n_routed_experts=8,
            max_position_embeddings=10240, param_dtype="bfloat16",
            init_weights=False))
        slots, chunk, max_len, blocks = 32, 512, 10240, 2049
    elif family == "deepseek-mla-mhc":
        # xing4-serve-repo-reason's programs: its widths, four residual
        # streams, the low-rank query, the YaRN tables to 262,144
        # positions, its lanes, table and chunk; two layers (one dense,
        # one of 8 experts), a small vocabulary and half of the pages
        # (a chunk's temporaries, 86 MB of four streams 3584 wide, have
        # to stay under half a layer's pool for `_pool_stays`)
        model = DeepseekV3ForCausalLM(DeepseekV3Config(
            vocab_size=512, hidden_size=3584, intermediate_size=9216,
            moe_intermediate_size=1024, num_hidden_layers=2,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=4,
            q_lora_rank=768, routed_scaling_factor=2.0, rope_theta=1e4,
            rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 4096},
            max_position_embeddings=262144, hc_mult=4,
            param_dtype="bfloat16", init_weights=False))
        slots, chunk, max_len, blocks = 16, 512, 24576, 12289
    elif family == "gpt-mha-d64":
        model = GPTForPretraining(GPTConfig(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
            max_seq_len=512, dropout=0.0, attn_dropout=0.0))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=512, hidden_size=512, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=512))
    eng = PagedServingEngine(model, num_slots=slots, max_len=max_len,
                             block_size=16, num_blocks=blocks,
                             prefill_chunk_len=chunk,
                             cache_dtype=jnp.bfloat16,
                             paged_kernel="pallas")
    pools = jax.tree_util.tree_leaves(eng._caches)
    assert [p.shape[1:] for p in pools] in (
        [(2, 16, 128)] * 2, [(2, 16, 256)] * 2, [(16, 640)] * 2)
    compiled = _compile_engine_program(eng, program, one_chip)
    _pool_stays(compiled, pools)
    _lane_state_stays(compiled, eng, program, pools)
    # a layer: the paged kernel; of the latent model the absorbed kernel
    # in each layer of a wave, none in a chunk (expanded), and the
    # expert kernel of its one expert layer in both
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == {
        ("deepseek-mla", "decode_wave"): 3,
        ("deepseek-mla", "prefill_chunk"): 1,
        ("deepseek-mla-mhc", "decode_wave"): 3,
        ("deepseek-mla-mhc", "prefill_chunk"): 1}.get((family, program), 2)
    # the maps and mixes of the four streams are in the one model's
    # programs and in no other's, and a wave's 20 Sinkhorn iterations
    # are not a loop on the device
    assert ("mhc_map" in text and "mhc_mix" in text) == \
        (family == "deepseek-mla-mhc")
    if (family, program) == ("deepseek-mla-mhc", "decode_wave"):
        assert " while(" not in text
    if family.startswith("deepseek") and program == "decode_wave":
        _latent_query_untransposed(text)


@pytest.mark.parametrize("program", ["decode_wave", "prefill_chunk"])
def test_dense_hybrid_engine_programs_keep_pool_and_state_in_place_for_v5e(
        one_chip, as_on_tpu, program):
    """granite4hm-serve-longdoc's programs as the engine builds them: the
    published widths, 32 slots, chunks of 512 (two scan chunks of 256),
    tables of 1,088 pages; one Mamba layer and one attention layer, a
    small vocabulary and an eighth of the pages, so that the sandbox
    holds the model. Neither the K/V pool ([blocks, 8, 16, 128]: 32
    query heads in groups of 4, head size 64) nor the slots' Mamba
    state (67 MB a layer) is copied, transposed or padded on its way
    through; one paged kernel."""
    import paddle_tpu as pt
    from paddle_tpu.nlp import GraniteHybridConfig, GraniteHybridForCausalLM
    from paddle_tpu.serving import PagedServingEngine

    pt.seed(0)
    slots, chunk, max_len = 32, 512, 17408
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        vocab_size=512, layer_types=("mamba", "attention"),
        param_dtype="bfloat16", init_weights=False))
    eng = PagedServingEngine(model, num_slots=slots, max_len=max_len,
                             block_size=16, num_blocks=4353,
                             prefill_chunk_len=chunk,
                             cache_dtype=jnp.bfloat16,
                             paged_kernel="pallas")
    pool, = eng._caches["kv"]
    ssm = eng._caches["state"][0]["ssm"]
    assert pool.shape == (4353, 8, 16, 128) and pool.dtype == jnp.bfloat16
    assert ssm.shape == (32, 64, 64, 128) and ssm.dtype == jnp.float32
    compiled = _compile_engine_program(eng, program, one_chip)
    _pool_stays(compiled, [pool, ssm])
    _lane_state_stays(compiled, eng, program,
                      jax.tree_util.tree_leaves(eng._caches))
    assert compiled.as_text().count("tpu_custom_call") == 1


def _expert_operands(txt):
    """The operand types of the one `moe_experts` call in a compiled
    program's text, in order."""
    line, = (ln for ln in txt.splitlines() if "%moe_experts" in ln
             and 'custom_call_target="tpu_custom_call"' in ln)
    kinds = line.split("operand_layout_constraints={", 1)[1].split("}}")[0]
    return re.findall(r"([a-z0-9]+\[[0-9,]*\])\{", kinds + "}")


def _walks_the_experts(txt, rows, experts, width, hidden, mats):
    """The call takes the resident rows first (a device trace's reader
    takes a Mosaic call whose first operand is an s32 array for paged
    attention), the stacks of matrices as they are stored, and then the
    walk (s32[experts]), the steps it runs and the groups' offsets."""
    assert _expert_operands(txt) == [
        f"bf16[{rows},{hidden}]", *[f"bf16[{experts},{width},{hidden}]"]
        * mats, f"s32[{experts}]", "s32[1]", f"s32[{experts + 1}]"]


@pytest.mark.parametrize("name,rows", [("wave-128-lanes", 768),
                                       ("ragged-rows", 100)])
def test_grouped_expert_kernel_compiles_for_v5e(one_chip, as_on_tpu, name,
                                                rows):
    """`moe_experts` at nemotron3n-serve-reason's size (128 experts of
    1856 x 2688, 768 picks a wave or a chunk): one kernel, under its
    name, and neither stack of matrices copied on the way in (both are
    stored [experts, width, hidden], which the chip tiles exactly)."""
    from paddle_tpu.ops.pallas.grouped_mlp import grouped_mlp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = sds((128, 1856, 2688), jnp.bfloat16)
    shapes = (sds((rows, 2688), jnp.bfloat16), w, w, sds((128,), jnp.int32))
    assert _kernel_names(grouped_mlp, *shapes) == ["moe_experts"]
    txt = jax.jit(grouped_mlp).lower(*shapes).compile().as_text()
    assert not re.search(r"bf16\[128,1856,2688\][^ ]* copy\(", txt)
    _walks_the_experts(txt, -(-rows // 16) * 16, 128, 1856, 2688, 2)


@pytest.mark.parametrize("name,rows", [("wave-32-lanes", 192),
                                       ("chunk-512", 3072)])
def test_gated_expert_kernel_compiles_for_v5e(one_chip, as_on_tpu, name,
                                              rows):
    """`moe_experts` in its gated form at kanana2-serve-doc's size (128
    experts of three 768 x 2048 matrices; 192 picks a wave, 3,072 a
    chunk of 512 tokens): a chunk's picks are one call, whose plan
    (98 MiB) holds them with the expert whole; one kernel, under its
    name, no stack of matrices copied on the way in."""
    from paddle_tpu.ops.pallas import grouped_mlp as gm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gated(x, up, gate, down, sizes):
        return gm.grouped_mlp(x, up, down, sizes, gate=gate)

    assert rows <= gm.max_rows(2048, 768, 2, 3)
    assert gm._tiles(rows, 2048, 768, 2, 3) == 1
    w = sds((128, 768, 2048), jnp.bfloat16)
    shapes = (sds((rows, 2048), jnp.bfloat16), w, w, w,
              sds((128,), jnp.int32))
    assert _kernel_names(gated, *shapes) == ["moe_experts"]
    txt = jax.jit(gated).lower(*shapes).compile().as_text()
    assert not re.search(r"bf16\[128,768,2048\][^ ]* copy\(", txt)
    _walks_the_experts(txt, rows, 128, 768, 2048, 3)


@pytest.mark.parametrize("name,rows,tiles", [("wave-16-lanes", 64, 1),
                                             ("chunk-512", 2048, 2)])
def test_wide_gated_expert_kernel_compiles_inside_vmem_for_v5e(
        one_chip, as_on_tpu, name, rows, tiles):
    """`moe_experts` in its gated form at xing4-serve-repo-reason's size
    (64 experts of three 1024 x 3584 matrices; 64 picks a wave, 2,048 a
    chunk of 512 tokens): whole, an expert's matrices double-buffered
    (44 MB) beside 2,048 resident rows (88 MB) pass a v5e's 128 MiB of
    VMEM, so the chunk's call walks an expert in two slices of its
    width; one kernel, under its name, no stack of matrices copied on
    the way in. The shapes that fitted before keep their one tile."""
    from paddle_tpu.ops.pallas import grouped_mlp as gm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gated(x, up, gate, down, sizes):
        return gm.grouped_mlp(x, up, down, sizes, gate=gate)

    assert gm._tiles(rows, 3584, 1024, 2, 3) == tiles
    assert gm._vmem(rows, 3584, 1024 // tiles, 2, 3) <= gm.VMEM_BUDGET \
        < 128 << 20
    # kanana2-serve-doc's and nemotron3n-serve-reason's calls
    assert gm._tiles(2048, 2048, 768, 2, 3) == 1
    assert gm._tiles(1024, 2688, 1856, 2, 2) == 1
    w = sds((64, 1024, 3584), jnp.bfloat16)
    shapes = (sds((rows, 3584), jnp.bfloat16), w, w, w,
              sds((64,), jnp.int32))
    assert _kernel_names(gated, *shapes) == ["moe_experts"]
    txt = jax.jit(gated).lower(*shapes).compile().as_text()
    assert not re.search(r"bf16\[64,1024,3584\][^ ]* copy\(", txt)
    _walks_the_experts(txt, rows, 64, 1024, 3584, 3)


@pytest.mark.parametrize("name,rows", [("wave-16-lanes", 128),
                                       ("chunk-512", 4096)])
def test_small_gated_experts_compile_in_one_call_for_v5e(one_chip, as_on_tpu,
                                                         name, rows):
    """`moe_experts` in its gated form at laguna-xs2-serve-agent-mix's
    size (256 experts of three 512 x 2048 matrices, top-8: 128 picks a
    wave of 16 lanes, 4,096 a chunk of 512 tokens): a chunk's picks are
    one call (`max_rows`), whose 4,096 resident rows (101 MB) beside an
    expert's matrices double-buffered (12.6 MB) fit the budget with the
    expert whole (116 MiB of 120); one kernel, under its name, no stack
    of matrices copied on the way in."""
    from paddle_tpu.ops.pallas import grouped_mlp as gm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gated(x, up, gate, down, sizes):
        return gm.grouped_mlp(x, up, down, sizes, gate=gate)

    assert 512 * 8 <= gm.max_rows(2048, 512, 2, 3)
    assert gm._tiles(rows, 2048, 512, 2, 3) == 1
    assert gm._vmem(rows, 2048, 512, 2, 3) <= gm.VMEM_BUDGET
    w = sds((256, 512, 2048), jnp.bfloat16)
    shapes = (sds((rows, 2048), jnp.bfloat16), w, w, w,
              sds((256,), jnp.int32))
    assert _kernel_names(gated, *shapes) == ["moe_experts"]
    txt = jax.jit(gated).lower(*shapes).compile().as_text()
    assert not re.search(r"bf16\[256,512,2048\][^ ]* copy\(", txt)
    _walks_the_experts(txt, rows, 256, 512, 2048, 3)


@pytest.mark.parametrize("name,experts,width,hidden,mats,tiles", [
    ("kanana2", 128, 768, 2048, 3, 2), ("xing4", 64, 1024, 3584, 3, 8),
    ("laguna", 256, 512, 2048, 3, 4), ("nemotron", 128, 1856, 2688, 2, 1)])
def test_a_long_forwards_segment_compiles_for_v5e(one_chip, as_on_tpu, name,
                                                  experts, width, hidden,
                                                  mats, tiles):
    """A full-sequence forward past the plan goes through the kernel a
    segment of `max_rows` picks at a time: at each routed cell's widths
    that segment, its expert in as many slices as `_tiles` cuts it,
    compiles inside the budget; one kernel, under its name, no stack of
    matrices copied on the way in."""
    from paddle_tpu.ops.pallas import grouped_mlp as gm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(x, sizes, *mats_):
        return gm.grouped_mlp(x, mats_[0], mats_[-1], sizes,
                              gate=mats_[1] if mats == 3 else None)

    rows = gm.max_rows(hidden, width, 2, mats)
    assert gm._tiles(rows, hidden, width, 2, mats) == tiles
    assert gm._vmem(rows, hidden, width // tiles, 2, mats) <= \
        gm.VMEM_BUDGET
    w = sds((experts, width, hidden), jnp.bfloat16)
    shapes = (sds((rows, hidden), jnp.bfloat16), sds((experts,), jnp.int32),
              *[w] * mats)
    assert _kernel_names(call, *shapes) == ["moe_experts"]
    txt = jax.jit(call).lower(*shapes).compile().as_text()
    assert not re.search(rf"bf16\[{experts},{width},{hidden}\][^ ]* copy\(",
                         txt)
    _walks_the_experts(txt, rows, experts, width, hidden, mats)


@pytest.mark.parametrize("program", ["decode_wave", "prefill_chunk"])
def test_window_pool_engine_programs_keep_both_pools_in_place_for_v5e(
        one_chip, as_on_tpu, program):
    """laguna-xs2-serve-agent-mix's programs as the engine builds them:
    the published widths and heads (48 on the full layer, 64 on the
    window layer, 8 kv-heads of 128), the YaRN and plain tables to
    262,144 positions, 16 slots, tables of 2,048 pages, chunks of 512;
    one full layer with the dense SwiGLU and one window layer with 8
    experts, a small vocabulary and a sixteenth of the full pool's pages,
    so that the host that compiles it holds the model. The full pool and the window
    pool (16 x 65 + 1 pages) are each aliased through, neither copied nor
    padded; a paged kernel a layer and the expert kernel."""
    import paddle_tpu as pt
    from paddle_tpu.nlp import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.serving import PagedServingEngine

    pt.seed(0)
    full, sliding = "full_attention", "sliding_attention"
    model = LagunaForCausalLM(LagunaConfig(
        vocab_size=512, num_hidden_layers=2, layer_types=[full, sliding],
        mlp_layer_types=["dense", "sparse"],
        num_attention_heads_per_layer=[48, 64], num_experts=8,
        rope_parameters={
            full: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                   "original_max_position_embeddings": 4096,
                   "beta_slow": 1, "beta_fast": 64,
                   "attention_factor": 1.4158883083359672,
                   "partial_rotary_factor": 0.5},
            sliding: {"rope_type": "default", "rope_theta": 10000}},
        param_dtype="bfloat16", init_weights=False))
    eng = PagedServingEngine(model, num_slots=16, max_len=32768,
                             block_size=16, num_blocks=2049,
                             prefill_chunk_len=512,
                             cache_dtype=jnp.bfloat16,
                             paged_kernel="pallas")
    assert (eng.window, eng.window_pages) == (512, 65)
    pools = jax.tree_util.tree_leaves(eng._caches)
    assert [p.shape for p in pools] == [(2049, 8, 16, 256),
                                        (16 * 65 + 1, 8, 16, 256)]
    compiled = _compile_engine_program(eng, program, one_chip)
    _pool_stays(compiled, pools[:1])
    _lane_state_stays(compiled, eng, program, pools)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert not re.search(r"bf16\[1041,8,16,256\]\S* (copy|transpose)\(",
                         text)


def test_a_one_stream_model_lowers_to_a_plain_residual():
    """`hc_mult` 1 (every model but one): the serving programs hold no
    `mhc_*` or `mla_q_lora` scope and the residual is `[B, L, hidden]`:
    two adds of that shape a layer (`x + attention`, `x + MLP`) and no
    other, so nothing was added for the streams."""
    import paddle_tpu as pt
    from paddle_tpu.nlp import DeepseekV3Config, DeepseekV3ForCausalLM
    from paddle_tpu.serving import PagedServingEngine

    pt.seed(0)
    layers, hidden, slots, chunk = 3, 64, 4, 16
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=96, hidden_size=hidden, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=layers,
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        num_experts_per_tok=3, max_position_embeddings=128))
    eng = PagedServingEngine(model, num_slots=slots, max_len=64,
                             block_size=8, prefill_chunk_len=chunk)
    for program, lead in (("decode_wave", f"{slots}x1"),
                          ("prefill_chunk", f"1x{chunk}")):
        fn, args = _engine_program_args(eng, program)
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert "mhc_" not in text and "mla_q_lora" not in text
        adds = re.findall(r"stablehlo\.add %\w+, %\w+ : tensor<"
                          + f"{lead}x{hidden}xf32>", text)
        assert len(adds) == 2 * layers


def test_latent_attention_forward_compiles_round_flash_for_v5e(one_chip,
                                                               as_on_tpu):
    """The sequence forward of a latent-attention layer at the published
    head sizes: keys of 192, values of 128 zero-extended to the keys'
    width, one flash kernel."""
    q = jax.ShapeDtypeStruct((2, 1024, 32, 192), jnp.bfloat16,
                             sharding=one_chip)

    def attend(q, k, v):
        return _flash_array(q, k, v, causal=True, layout="bshd",
                            scale=192 ** -0.5)[..., :128]

    assert _kernel_names(attend, q, q, q) == ["flash_fwd"]


def test_kernels_carry_their_names_for_v5e(one_chip, as_on_tpu):
    """A `jax.named_scope` right at each `pallas_call` names the
    instruction, under grad and jit alike, so that a device trace can be
    read by name (`benchmark/trace_reduce.kernel_class` prints the same
    four)."""
    x = jax.ShapeDtypeStruct((4, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return _flash_array(q, k, v, causal=True, layout="bshd").astype(
            jnp.float32).sum()

    assert _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]

    def wave(q, pool, tables, pos):
        return pa.attend(q, pool, tables, pos, 64 ** -0.5, kernel="pallas")

    assert _kernel_names(wave, *_paged_args(one_chip, 8, 12, 12, 1, 64)) \
        == ["paged_attention"]
