"""Batched in-graph sampling: temperature / top-k / top-p / greedy, per-slot
parameters so one jitted decode step serves heterogeneous requests.

The reference carries these as SamplingOptions (protocols/common.rs) into the
external engine; here they become dense per-slot arrays so the whole sampler
lives inside the decode XLA program (no logits transfer off-device — only
sampled ids and chosen logprobs leave HBM).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclasses.dataclass
class SlotSampling:
    """Host-side staging of per-slot sampling params (converted to arrays)."""

    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0            # 0 → disabled
    top_p: float = 1.0
    seed: int = 0

    @classmethod
    def from_options(cls, opts, default_temperature: float = 0.7) -> "SlotSampling":
        if opts is None:
            return cls(temperature=default_temperature)
        if getattr(opts, "greedy", False):
            return cls(temperature=0.0, seed=opts.seed or 0)
        t = opts.temperature if opts.temperature is not None else default_temperature
        return cls(temperature=float(t),
                   top_k=int(opts.top_k or 0),
                   top_p=float(opts.top_p if opts.top_p is not None else 1.0),
                   seed=int(opts.seed or 0))


def pack_sampling(slots: list) -> dict:
    """[SlotSampling] → dict of np arrays for the jitted sampler."""
    return {
        "temperature": np.array([s.temperature for s in slots], np.float32),
        "top_k": np.array([s.top_k for s in slots], np.int32),
        "top_p": np.array([s.top_p for s in slots], np.float32),
    }


@jax.named_scope("sampling")
def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """logits: [B, V]; keys: [B] PRNG keys; per-slot params [B].
    Returns (tokens [B] int32, logprobs [B] float32 of the chosen token
    under the unscaled distribution)."""
    B, V = logits.shape
    logprobs_all = jax.nn.log_softmax(logits, axis=-1)

    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (V,), dtype=jnp.float32))(keys)

    def _plain(_):
        # no top-k/top-p anywhere in the batch: Gumbel-argmax IS exact
        # temperature sampling, and skips the [B, V] argsort that would
        # otherwise dominate the decode step at 100k+ vocabs
        return jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)

    def _filtered(_):
        order = jnp.argsort(-scaled, axis=-1)                   # [B, V] desc
        sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        keep_p = (cum - sorted_probs) < top_p[:, None]
        k_eff = jnp.where(top_k > 0, top_k, V)[:, None]
        keep_k = jnp.arange(V)[None, :] < k_eff
        keep = keep_p & keep_k
        keep = keep.at[:, 0].set(True)
        masked = jnp.where(keep, sorted_logits, NEG_INF)
        sorted_gumbel = jnp.take_along_axis(gumbel, order, axis=-1)
        choice_sorted = jnp.argmax(masked + sorted_gumbel, axis=-1)
        return jnp.take_along_axis(
            order, choice_sorted[:, None], axis=-1)[:, 0].astype(jnp.int32)

    need_filter = jnp.any((top_p < 1.0) | (top_k > 0))
    sampled_tok = jax.lax.cond(need_filter, _filtered, _plain, None)

    tok = jnp.where(temperature <= 0.0, greedy_tok, sampled_tok)
    chosen_logprob = jnp.take_along_axis(
        logprobs_all, tok[:, None], axis=-1)[:, 0]
    return tok, chosen_logprob


def make_slot_keys(base_seed, slot_seeds: jax.Array,
                   steps: jax.Array) -> jax.Array:
    """Deterministic per-(request-seed, request-step) PRNG keys: a request
    with an explicit seed reproduces its stream regardless of which slot it
    lands in or what else is batched with it. `steps` is each slot's OWN
    generated-token count (not a global counter). ``base_seed`` is the
    engine's seed or the key already made from it (``PRNGKey(seed)``): a
    program that takes the key as an argument serves every seed, one that
    closes over the number is compiled anew for each."""
    base = (jax.random.PRNGKey(base_seed) if jnp.ndim(base_seed) == 0
            else base_seed)
    steps = jnp.broadcast_to(jnp.asarray(steps), slot_seeds.shape)

    def mk(seed, step):
        return jax.random.fold_in(jax.random.fold_in(base, seed), step)

    return jax.vmap(mk)(slot_seeds, steps)
