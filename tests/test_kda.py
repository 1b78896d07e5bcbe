"""``engine/kda.py``: the chunked (WY / UT) form and the one-token step of
Kimi Delta Attention against the token-by-token recurrence, interpreted on
the CPU (the kernels' own bodies). Builds for the chip:
``tests/test_tpu_compile_kimi_linear.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kda
from dynamo_tpu.engine.kda import (CHUNK, kda_chunk, kda_recurrence,
                                   kda_step)

H, D = 2, 16


def _inputs(T, seed, g_min=-1.0, beta=None, like_keys=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (T, H, D))
    k = jax.nn.silu(jax.random.normal(ks[1], (T, H, D)))
    if like_keys:       # every key nearly the same direction
        k = k[:1] + 0.05 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, D))
    g = g_min * jax.random.uniform(ks[3], (T, H, D))
    b = (jax.nn.sigmoid(jax.random.normal(ks[4], (T, H))) if beta is None
         else jnp.full((T, H), beta))
    s0 = jax.random.normal(ks[5], (H, D, D))
    return q, k, v, g, b, s0


_chunk = jax.jit(lambda *a: kda_chunk(*a, interpret=True))


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("T", [CHUNK, 2 * CHUNK, 100, 37, 1])
def test_chunk_form_is_the_recurrence(T):
    """Lengths that do and do not divide into chunks of CHUNK rows."""
    args = _inputs(T, seed=T)
    o, S = kda_recurrence(*args)
    o2, S2 = _chunk(*args)
    _close(o, o2)
    _close(S, S2)


def _by_channel(args):
    """The decay kept in every other key channel of head 0 and in the
    first quarter of head 1's, none in the rest."""
    q, k, v, g, b, s0 = args
    kept = jnp.stack([jnp.arange(D) % 2 == 0, jnp.arange(D) < D // 4])
    return q, k, v, jnp.where(kept[None], g, 0.0), b, s0


@pytest.mark.parametrize("g_min", [-20.0, -5.0, -1e-3, 0.0, "by channel"])
def test_chunk_form_holds_under_strong_and_no_decay(g_min):
    """g down to -20 a token: exp(-gamma_j) would overflow float32 after
    five tokens; every exponent taken is <= 0, so nothing does. "by
    channel": -20 a token in some key channels of a head and none in the
    others, which one decay a head (DeltaNet's) cannot express."""
    if g_min == "by channel":
        args = _by_channel(_inputs(2 * CHUNK, seed=3, g_min=-20.0))
    else:
        args = _inputs(160, seed=3, g_min=g_min)
    o, S = kda_recurrence(*args)
    o2, S2 = _chunk(*args)
    assert bool(jnp.isfinite(o2).all()) and bool(jnp.isfinite(S2).all())
    _close(o, o2)
    _close(S, S2)


@pytest.mark.parametrize("beta", [0.0, 1e-4, 1.0 - 1e-4, 1.0])
@pytest.mark.parametrize("like_keys", [False, True])
def test_chunk_form_at_the_ends_of_beta(beta, like_keys):
    """beta -> 0 (nothing written) and -> 1 (the key's old value replaced),
    with keys nearly alike too: forward substitution, where a Neumann
    series of (I + A)^-1 would cancel catastrophically."""
    args = _inputs(2 * CHUNK, seed=11, g_min=-0.05, beta=beta,
                   like_keys=like_keys)
    o, S = kda_recurrence(*args)
    o2, S2 = _chunk(*args)
    _close(o, o2, 1e-4)
    _close(S, S2, 1e-4)


@pytest.mark.parametrize("cut", [CHUNK, 50, 3])
def test_state_carried_across_two_dispatches_equals_one(cut):
    args = _inputs(CHUNK + 50, seed=5)
    q, k, v, g, b, s0 = args
    o, S = _chunk(*args)
    o1, S1 = _chunk(q[:cut], k[:cut], v[:cut], g[:cut], b[:cut], s0)
    o2, S2 = _chunk(q[cut:], k[cut:], v[cut:], g[cut:], b[cut:], S1)
    _close(jnp.concatenate([o1, o2]), o)
    _close(S2, S)


@pytest.mark.parametrize("true_len", [1, 40, CHUNK, 70])
def test_rows_past_true_len_leave_the_state_alone(true_len):
    """A padded bucket: g = 0 and beta = 0 past true_len, as the engine
    sets them; the state out is the one at true_len."""
    q, k, v, g, b, s0 = _inputs(2 * CHUNK, seed=9)
    valid = jnp.arange(2 * CHUNK) < true_len
    gm = jnp.where(valid[:, None, None], g, 0.0)
    bm = jnp.where(valid[:, None], b, 0.0)
    o, S = _chunk(q, k, v, gm, bm, s0)
    o_ref, S_ref = kda_recurrence(q[:true_len], k[:true_len], v[:true_len],
                                  g[:true_len], b[:true_len], s0)
    _close(o[:true_len], o_ref)
    _close(S, S_ref)


def _matrices_f64(q, k, v, g, beta):
    """One head's chunk, float64 numpy, as the module docstring writes the
    matrices: q, k, v, g [C, d]; beta [C]."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    gam = np.cumsum(g, axis=0)
    decay = np.exp(np.minimum(gam[:, None] - gam[None], 0.0))    # [i, j, c]
    A = beta[:, None] * np.tril(np.einsum("ic,jc,ijc->ij", k, k, decay), -1)
    B = np.tril(np.einsum("ic,jc,ijc->ij", q, k, decay))
    T = np.linalg.inv(np.eye(len(A)) + A)
    return {"W": T @ (beta[:, None] * k * np.exp(gam)),
            "Uv": T @ (beta[:, None] * v), "Qd": q * np.exp(gam), "B": B,
            "Kt": (k * np.exp(gam[-1] - gam)).T, "dC": np.exp(gam[-1])}


@functools.lru_cache(maxsize=None)
def _prepared(decay):
    """``kda_prepare``'s outputs for two chunks of two heads, once a kind
    of decay, with the inputs it was given."""
    args = _inputs(2 * CHUNK, seed=21, g_min=-3.0)
    if decay == "by channel":
        args = _by_channel(args)
    W, Uv, Qd, B, Kx = (np.asarray(a) for a in kda._prepare(
        *(a.reshape(2, CHUNK, -1) for a in args[:5]), interpret=True))
    return args, {"W": W, "Uv": Uv, "Qd": Qd, "B": B, "Kt": Kx[..., :CHUNK],
                  "dC": Kx[..., CHUNK], "dC over its lanes": Kx[..., CHUNK:]}


@pytest.mark.parametrize("decay", ["every channel", "by channel"])
@pytest.mark.parametrize("name", ["W", "Uv", "Qd", "B", "Kt", "dC"])
def test_prepare_builds_the_chunk_matrices(name, decay):
    """The six things the walk takes, each tile [chunk, head] against a
    float64 build of the same matrices; the chunk's decay repeated over
    the lanes it shares K^^T's tile with."""
    (q, k, v, g, b, _), got = _prepared(decay)
    for n in range(2):
        for h in range(H):
            rows = slice(n * CHUNK, (n + 1) * CHUNK)
            want = _matrices_f64(q[rows, h], k[rows, h], v[rows, h],
                                 g[rows, h], b[rows, h])[name]
            _close(got[name][n, h], want, 1e-5)
            if name == "dC":
                full = got["dC over its lanes"][n, h]
                assert (full == full[:, :1]).all()


@pytest.mark.parametrize("layer", [0, 2])
def test_step_updates_its_layer_in_place(layer):
    """One token for each of four slots at ``layer`` of a three-layer state
    array: the other layers' rows stay bit-equal; alpha 0 starts a slot from
    zero; alpha 1 with beta 0 leaves a slot as it is."""
    B = 4
    q, k, v, g, b, _ = _inputs(B, seed=13)
    state = jax.random.normal(jax.random.PRNGKey(2), (3 * B, H, D, D))
    alpha = jnp.exp(g).at[1].set(0.0).at[2].set(1.0)
    b = b.at[2].set(0.0)
    o, new = jax.jit(lambda *a: kda_step(*a, interpret=True))(
        q, k, v, alpha, b, state, jnp.int32(layer))
    lo = layer * B
    for slot in range(B):
        s0 = state[lo + slot] * (0.0 if slot == 1 else 1.0)
        gs = jnp.zeros_like(g[slot]) if slot in (1, 2) else g[slot]
        o_ref, S_ref = kda_recurrence(
            q[slot][None], k[slot][None], v[slot][None], gs[None],
            b[slot][None], s0)
        _close(o[slot], o_ref[0])
        _close(new[lo + slot], S_ref)
    assert bool((new[lo + 2] == state[lo + 2]).all())
    rest = np.r_[0:lo, lo + B:3 * B]
    assert bool((new[rest] == state[rest]).all())


def test_step_after_chunk_continues_the_sequence():
    """Prefill by the chunk form, then decode by the step: the recurrence
    over the whole sequence."""
    T = 70
    q, k, v, g, b, _ = _inputs(T + 3, seed=17)
    zero = jnp.zeros((H, D, D))
    o_all, S_all = kda_recurrence(q, k, v, g, b, zero)
    _, S = _chunk(q[:T], k[:T], v[:T], g[:T], b[:T], zero)
    state = S[None]
    for t in range(T, T + 3):
        o, state = kda_step(q[t][None], k[t][None], v[t][None],
                            jnp.exp(g[t])[None], b[t][None], state,
                            jnp.int32(0), interpret=True)
        _close(o[0], o_all[t])
    _close(state[0], S_all)
