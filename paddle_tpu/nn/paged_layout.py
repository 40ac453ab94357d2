"""What a served model keeps in the paged cache, declared once a model
(`paged_layout()`): the paged serving engine allocates, packs, counts and
refuses from it and reads nothing else of the model's kind (docs/
serving.md, "What a model declares to be served"). The pool forms and the
functions the counts come from are `nn/paged_attention.py`'s."""
import dataclasses

import jax
import numpy as np

from . import paged_attention as pa

#: why a layout is refused what moves the K/V pages of one pool (`refusal`)
SLOT_STATE_REFUSAL = (
    "{what} moves K/V pages only, and this model keeps a recurrent state "
    "a slot beside them (its layout's slot_record) that no page holds: a "
    "snapshot of that state at the boundary is needed first")
LATENT_REFUSAL = (
    "{what} was written for pools of K/V pages ([blocks, kv_heads, "
    "block, 2 x head_dim]); this model's pages hold latent rows (a "
    "latent_cache: [blocks, block, width], one row a position shared by "
    "every head) and the program has not been taken through that form yet")
WINDOW_REFUSAL = (
    "{what} moves the pages of one pool; this model's window layers keep "
    "theirs in a second pool (its layout's window pool) that gives back "
    "the pages behind the window as a lane advances, and the program has "
    "not been taken through two pools yet")


@dataclasses.dataclass(frozen=True)
class AttentionGroup:
    """`layers` attention layers alike in what the engine counts: form
    "kv" (pages of [kv_heads, block, 2 x head_dim]) or "latent" (rows all
    `heads` share; `latent` is (rank, rope, nope, v), what `latent_path`
    chooses by); `window` the keys a query attends back, None for all;
    `pool` "full", or "window": a pool of its own whose pages behind the
    window go back as a lane advances."""
    layers: int
    form: str
    heads: int
    kv_heads: int = 1
    head_dim: int = 0
    latent: tuple = None
    window: int = None
    pool: str = "full"

    def steps(self, start, c, bs, nblk, itemsize):
        """(run, visited) grid steps of one layer's kernel for a call of
        `c` queries a lane at `start` [B]: none for a latent call on the
        expanded path, which runs no kernel."""
        if self.form == "kv":
            return pa.count_steps(start, c, self.heads // self.kv_heads,
                                  self.kv_heads, self.head_dim, bs, nblk,
                                  itemsize, self.window)
        if pa.latent_path(c, *self.latent) != "absorbed":
            return 0, 0
        return pa.count_latent_steps(start, c, self.heads,
                                     pa.latent_width(*self.latent[:2]), bs,
                                     nblk, itemsize)

    def pages(self, start, c, bs, nblk):
        """Table entries one layer attends for `c` queries a lane at
        `start` [B] (`attended_pages`), summed over the lanes."""
        lo, hi = pa.attended_pages(start, c, bs, nblk, self.window)
        return int(np.sum(hi - lo))


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """A served model's paged cache and the work it does a token: its
    attention `groups` (the page and step counters count one layer of a
    layout of one group, each group times its layers where there are
    several); `slot_record`, a record a slot beside the pages (the cache is
    {"kv": pools, "state": arrays of leading dimension num_slots}) that
    the engine zeroes when the slot begins a prompt; the (token, expert)
    pairs and stream-mapped sub-layers one token makes, the experts the
    sparse layers store and the tokens one call of each sparse layer's
    expert kernel takes (`RoutedExperts.tokens_a_call`)."""
    groups: tuple
    slot_record: bool = False
    moe_picks: int = 0
    mhc_rows_mixed: int = 0
    experts_stored: int = 0
    expert_tokens: tuple = ()

    @property
    def window(self):
        """The window pool's window (it serves window layers of one size
        beside full ones), None without one."""
        sizes = sorted({g.window for g in self.groups if g.pool == "window"})
        if len(sizes) > 1:
            raise ValueError(f"window layers of sizes {sizes}: one window "
                             "pool serves one window")
        return sizes[0] if sizes else None

    @property
    def latent(self):
        """Layers counted whose pages hold latent rows."""
        return sum(n for g, n in self.weighted() if g.form == "latent")

    @property
    def shares_prefixes(self):
        """Not beside records no page holds, nor where pages go back."""
        return not self.slot_record and self.window is None

    @property
    def counts_work(self):
        """Whether the engine reports counts of the model's own layers."""
        return bool(self.slot_record or self.latent or self.window
                    or self.moe_picks or self.mhc_rows_mixed)

    def weighted(self):
        """(group, the layers its counts stand for) of each group."""
        return [(g, g.layers if len(self.groups) > 1 else 1)
                for g in self.groups]

    def refusal(self, what):
        """None, or why this layout cannot take `what`, an operation that
        moves, shares or rolls back the K/V pages of one pool alone."""
        for refused, why in ((self.slot_record, SLOT_STATE_REFUSAL),
                             (self.latent, LATENT_REFUSAL),
                             (self.window, WINDOW_REFUSAL)):
            if refused:
                return why.format(what=what)
        return None

    def chunk_calls(self, n):
        """(expert-kernel calls, sparse layers) of a chunk of `n` tokens."""
        return (sum(-(-n // t) for t in self.expert_tokens),
                len(self.expert_tokens))

    def wave_counts(self, positions, slots):
        """`MODEL_COUNTS` of a wave whose lanes decode at `positions`: it
        steps all `slots`' records; a latent layer reads position + 1 rows."""
        lanes = len(positions)
        return {"moe_picks": lanes * self.moe_picks,
                "mhc_rows_mixed": lanes * self.mhc_rows_mixed,
                "ssm_records_stepped": slots * self.slot_record,
                "ssm_lanes_stepped": lanes * self.slot_record,
                "mla_rows_attended": self.latent and self.latent * int(
                    np.sum(positions + 1))}

    def chunk_counts(self, start, valid, c, bs, nblk):
        """`MODEL_COUNTS` of a chunk of `valid` tokens, `c` wide, at `start`."""
        return {"moe_picks": valid * self.moe_picks,
                "mhc_rows_mixed": valid * self.mhc_rows_mixed,
                "mla_rows_expanded": self.latent and self.latent * int(
                    pa.expanded_rows(start, c, bs, nblk))}

    def health(self, caches):
        """The /healthz keys that say what this layout serves."""
        kinds = {"slot_state": self.slot_record, "latent_cache": bool(
            self.latent), "window": self.window}
        h = {k: v for k, v in kinds.items() if v}
        if self.slot_record:
            h["state_bytes"] = sum(a.nbytes for a in
                                   jax.tree_util.tree_leaves(caches["state"]))
        return h
