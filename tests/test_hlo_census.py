"""Chip-independent perf evidence: the graph properties behind the
projected-MFU claims, asserted on the traced+DCE'd train step so they
cannot regress between chip runs.

Property 1 — BSHD flash layout: zero bf16 attention-layout transposes
  in the whole step (fwd+bwd+optimizer). Each such transpose is an HBM
  round-trip of a [B,H,S,D] activation (August trace, hotspot #1).
Property 2 — vocab-chunked fused head+CE: no [.., S, .., V] intermediate
  anywhere; the [B,S,V] logits (1 GiB at gpt2s b=8 f32) never exist
  (August trace, hotspot #2). Ref framework computes full logits then
  softmax_with_cross_entropy (ref python/paddle/fluid/layers/loss.py).

Positive controls: the BHSD layout must show the transposes and the
unfused loss must show the logits tensor — proving the census detects
what it claims to rule out. Census lives in
paddle_tpu/utils/graph_census.py (same technique as
scripts/scaling_probe.py's collective census).
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.jit import TrainStep
from paddle_tpu.nlp import GPTConfig, GPTForPretraining
from paddle_tpu.nlp.gpt import gpt_pretrain_loss
from paddle_tpu.utils.graph_census import census_jaxpr, trace_train_step

SEQ, HEAD_DIM, VOCAB = 1024, 64, 32768


def _census(layout, fused, medium=False, recompute=False):
    pt.seed(0)
    if medium:
        # BASELINE configs[3] topology (gpt2-medium, bench_sweep.py)
        cfg = GPTConfig(vocab_size=VOCAB, hidden_size=1024, num_layers=24,
                        num_heads=16, max_seq_len=SEQ, dropout=0.0,
                        attn_dropout=0.0, attn_layout=layout,
                        fused_head_loss=fused)
    else:
        # BASELINE configs[1] topology (gpt2-small, bench_sweep.py)
        cfg = GPTConfig(vocab_size=VOCAB, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=SEQ, dropout=0.0,
                        attn_dropout=0.0, attn_layout=layout,
                        fused_head_loss=fused)
    model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    if recompute:
        from paddle_tpu.distributed.fleet.meta_optimizers import \
            RecomputeOptimizer
        opt = RecomputeOptimizer(opt)
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=False)
    ids = np.random.RandomState(0).randint(
        0, VOCAB, (2, SEQ)).astype("int32")
    head_dim = cfg.hidden_size // cfg.num_heads
    return census_jaxpr(trace_train_step(step, ids, ids),
                        seq_len=SEQ, head_dim=head_dim, vocab_size=VOCAB)


def test_gpt2s_bshd_fused_is_clean():
    c = _census("bshd", fused=True)
    assert c["attn_transposes"] == 0, c["attn_transpose_shapes"]
    assert c["vocab_intermediates"] == 0, c["vocab_shapes"]
    # flash fwd + bwd kernels actually present (not silently fallen back)
    assert c["pallas_calls"] >= 24, c  # >= 2 per layer x 12 layers


def test_gpt2s_bhsd_fused_no_vocab_intermediate():
    """BHSD keeps the fused CE property; its transposes are the cost the
    BSHD path removes — the positive control that the census sees them."""
    c = _census("bhsd", fused=True)
    assert c["vocab_intermediates"] == 0, c["vocab_shapes"]
    assert c["attn_transposes"] > 0, (
        "census failed to detect BHSD layout transposes — predicate broken")


def test_gpt2s_unfused_shows_logits():
    """Positive control for property 2: the unfused loss must show the
    [B,S,V] materialisation the chunked CE exists to remove."""
    c = _census("bshd", fused=False)
    assert c["vocab_intermediates"] > 0
    assert any(VOCAB in s and SEQ in s for s in c["vocab_shapes"])


@pytest.mark.slow
def test_gpt2m_recompute_bshd_fused_is_clean():
    """gpt2-medium exactly as bench_sweep runs it (recompute + bf16):
    the census recurses remat sub-jaxprs, so a transpose or logits
    materialisation reintroduced under checkpointing still fails."""
    c = _census("bshd", fused=True, medium=True, recompute=True)
    assert c["attn_transposes"] == 0, c["attn_transpose_shapes"]
    assert c["vocab_intermediates"] == 0, c["vocab_shapes"]
    assert c["pallas_calls"] >= 48, c  # >= 2 per layer x 24 layers


def test_bert_mha_bshd_no_attn_transposes():
    """The MultiHeadAttention bshd path (BERT-base topology, bench_sweep
    sweep_bert shapes) must leave zero attention-layout transposes in
    the traced train step — same property the GPT census pins, now on
    the shared nn.MultiHeadAttention used by BERT/Transformer."""
    from paddle_tpu.nlp.bert import (BertForPretraining, bert_base,
                                     bert_pretrain_loss)

    pt.seed(0)
    cfg = bert_base(max_seq_len=512, dropout=0.0, attn_dropout=0.0)
    import os
    counts = {}
    for layout in ("bhsd", "bshd"):
        os.environ["PT_ATTN_LAYOUT"] = layout
        try:
            pt.seed(0)
            model = BertForPretraining(cfg)
            model.to(dtype=jnp.bfloat16)
            opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
            step = TrainStep(model, bert_pretrain_loss, opt, donate=False)
            rng = np.random.RandomState(0)
            ids = rng.randint(0, cfg.vocab_size, (2, 512)).astype("int32")
            mlm = np.where(rng.rand(2, 512) < 0.15,
                           rng.randint(0, cfg.vocab_size, (2, 512)),
                           -100).astype("int64")
            nsp = rng.randint(0, 2, (2,)).astype("int64")
            c = census_jaxpr(
                trace_train_step(step, (ids,), (mlm, nsp)),
                seq_len=512, head_dim=64, vocab_size=cfg.vocab_size)
            counts[layout] = c
        finally:
            os.environ.pop("PT_ATTN_LAYOUT", None)
    assert counts["bshd"]["attn_transposes"] == 0, \
        counts["bshd"]["attn_transpose_shapes"]
    assert counts["bhsd"]["attn_transposes"] > 0, (
        "census failed to detect the BHSD transposes — predicate broken")
