"""Latent attention core, prefill: latent rows the window's chunks put
through the expansion (`mla_rows_expanded`, a layer) over the prompt
tokens those chunks carried (`prefill_tokens`): what a prompt token pays
to have its prefix expanded again. A fresh 8k document reads about 9 (a
chunk of 512 expands 512 to 8.7k rows); a hit's short question expands
its whole resident prefix for a few hundred tokens, 30 and more."""
LAYER, SOURCE = "latent_attention_core", "program_counter"


def read(ctx):
    obs = ctx["obs"]
    if "snap0" not in obs or "mla_rows_expanded" not in obs["snap1"]:
        return None
    tokens = obs["snap1"].get("prefill_tokens", 0) - \
        obs["snap0"].get("prefill_tokens", 0)
    rows = obs["snap1"]["mla_rows_expanded"] - \
        obs["snap0"].get("mla_rows_expanded", 0)
    return rows / tokens if tokens else None
