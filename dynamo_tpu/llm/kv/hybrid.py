"""The kinds of per-sequence memory of a model whose layers are not all
alike (docs/hybrid_cache.md), as the block manager sees them.

A uniform model keeps one paged row per token per layer; ``KvBlockManager``
pages them and nothing else exists. A hybrid model (``models/sambay.py``)
keeps three kinds, and only the first is made of blocks that the pool hands
out and takes back:

* **paged**: rows of the layers that attend over the whole context, under
  the block table, allocated a block at a time as the context grows and
  released at finish, cancel and preemption, as ever;
* **window**: rows of the layers that attend over the last ``window``
  positions: a ring of ``ring_blocks`` blocks per slot and layer, inside
  arrays sized by ``max_num_seqs``. A slot owns its rings for as long as it
  holds a request; nothing is allocated or released, and a context of any
  length holds ``ring_blocks`` blocks a layer;
* **state**: a fixed number of bytes per slot and state-space layer, owned
  the same way. It is not made of tokens: a block's hash says nothing of
  the state at its boundary, so a prefix hit could not be resumed. A
  manager with a layout that has state therefore matches no prefix and
  registers no block (``KvBlockManager.enable_reuse`` is forced off: the
  router is told of no block it could not use).

The three kinds above are ``models/sambay.py``'s layout. ``models/mla.py``'s
dots3_note has two groups of layers, both made of pool blocks
(``window_pool``): paged rows of the full-attention layers (TWO arrays under
one block id: the latent and the index key) and window rows of ANOTHER width
for the window layers, under block ids of a pool of their own. Window blocks
are allocated as a context grows and released once wholly behind the window,
so a running sequence holds at most ``ring_blocks`` of them a layer at any
length; a released block whose hash is registered stays as evictable cache,
and a prefix hit needs the paged blocks of the whole prefix AND the window
blocks of its last ``window`` rows (``KvBlockManager``,
docs/hybrid_cache.md). With no state, reuse stays on.

``models/mimo.py``'s mimo_v2 has the same two groups with plain
grouped-query rows: K and V arrays of the full layers under the paged ids
(``kv["k"]``, ``kv["v"]``), K and V arrays of the window layers under the
window pool's (``kv["win_k"]``, ``kv["win_v"]``), and there the window rows
are the wider ones (``window_row_bytes``; docs/hybrid_cache.md part three).

``models/kimi_linear.py``'s kimi_linear is the fourth layout: a paged LATENT
group (the 7 full-attention layers' 640-lane rows under one block id,
``kv["kv"]``) and a STATE group (20 delta-attention layers, per slot a
float32 ``[heads, dim, dim]`` matrix and the convolutions' last inputs:
``kv["kda"]``, ``kv["conv"]``; 2.17 MB a slot and layer at the published
widths, whatever the context), and no window. The state group is
``models/sambay.py``'s kind, so ``has_state`` keeps reuse off here too: no
prefix hit, no block announced to the router (docs/hybrid_cache.md part
five). ``bytes_by_kind`` sizes it as the first layout: a window group of no
layers holds no bytes.
"""

from __future__ import annotations

import dataclasses


# the evictable part of a window pool (HybridCacheLayout.window_pool_blocks):
# hit boundaries kept a slot, and its bytes against the paged pool's
WINDOW_TAILS_PER_SLOT = 4
WINDOW_CACHE_BYTES_RATIO = (3, 2)


@dataclasses.dataclass(frozen=True)
class HybridCacheLayout:
    block_size: int
    row_bytes: int             # one token's K and V rows of one layer
    paged_layers: int          # layers whose rows are paged...
    readers_of_paged: int      # ...and the layers that read those rows
    window_layers: int
    window: int
    state_layers: int
    state_bytes: int           # one slot's state of one layer
    # window rows are blocks of a second pool (dots3_note, mimo_v2), not
    # per-slot rings (phi4flash)
    window_pool: bool = False
    # one token's rows of one WINDOW layer, where they are of another width
    # than the paged layers' (0: row_bytes)
    window_row_bytes: int = 0
    # a paged row of some layer is computed from the token AFTER its own (a
    # resident multi-token-prediction block's row p takes x_{p+1}:
    # models/mimo.py). A block's hash covers its own tokens, so the last
    # row of a prefix hit was computed from its PRODUCER's next token: a hit
    # is cut back by one block and that block is computed again
    rows_read_next_token: bool = False
    # positions by which a decode step's rows may lie beyond where harvested
    # state would put them: a resident drafter's step is queued behind one
    # that advances its slot by one position or by two, and only the device
    # knows which (1; 0 wherever a queued step's positions are known)
    rows_ahead: int = 0

    @property
    def ring_blocks(self) -> int:
        """Blocks of one window layer's ring: the window and one more, so
        that the block being written holds no row the window still needs
        (the rows of a two-row step included); ``rows_ahead`` widens the
        span that one table has to cover by as many positions."""
        return -(-(self.window + self.rows_ahead) // self.block_size) + 1

    @property
    def has_state(self) -> bool:
        return self.state_layers > 0

    @property
    def window_reach_blocks(self) -> int:
        """Window blocks before a block boundary P that a prefix hit ending
        at P needs: those of the rows [P - window, P)."""
        return -(-self.window // self.block_size)

    def window_pool_blocks(self, num_blocks: int, max_num_seqs: int,
                           prefill_tokens: int) -> int:
        """Blocks of the window pool that goes with a paged pool of
        ``num_blocks``: what every slot's live window and one prefill
        dispatch of ``prefill_tokens`` can hold at once (an allocation
        there never fails), and an evictable part: the window sibling of
        half the paged pool's blocks, but no more than
        WINDOW_TAILS_PER_SLOT hit boundaries' worth a slot (a cached window
        block serves a hit only as one of the ``window_reach_blocks`` before
        the boundary the hit ends at) and never more bytes than
        WINDOW_CACHE_BYTES_RATIO of the paged pool's: a window block may be
        several times a paged one (mimo_v2: 6.7), and the count alone would
        then ask for more cache than the pool it serves. Derived, not a
        flag (docs/hybrid_cache.md part three)."""
        live = max_num_seqs * self.ring_blocks
        prefill = -(-prefill_tokens // self.block_size) + self.ring_blocks
        paged_block = self.paged_layers * self.row_bytes
        window_block = self.window_layers * (self.window_row_bytes
                                             or self.row_bytes)
        num, den = WINDOW_CACHE_BYTES_RATIO
        cached = min(
            num_blocks // 2,
            WINDOW_TAILS_PER_SLOT * max_num_seqs * self.window_reach_blocks,
            (num * num_blocks * paged_block) // (den * window_block))
        return 1 + live + prefill + cached

    def blocks_by_kind(self, context_tokens: int) -> dict:
        """Blocks (state: slots) that one sequence of ``context_tokens``
        holds, per layer of each kind."""
        bs = self.block_size
        return {"paged": -(-context_tokens // bs),
                "window": min(-(-context_tokens // bs), self.ring_blocks),
                "state": 1}

    def bytes_by_kind(self, num_blocks: int, max_num_seqs: int) -> dict:
        """Device bytes of each kind for a pool of ``num_blocks`` and
        ``max_num_seqs`` slots: sambay's three kinds, or kimi_linear's
        paged latent rows and matrix state (no window layers: 0 bytes)."""
        block = self.block_size * self.row_bytes
        if self.window_pool:
            raise ValueError("a window pool is sized by window_pool_blocks")
        return {
            "paged": self.paged_layers * num_blocks * block,
            "window": (self.window_layers * max_num_seqs
                       * self.ring_blocks * block),
            "state": self.state_layers * max_num_seqs * self.state_bytes}
