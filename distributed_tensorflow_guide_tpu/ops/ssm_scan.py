"""The forward of a selective state-space layer, in the two forms serving
needs: a run of positions from a carried state, and one position for every
row. Mamba-2's recurrence first (one decay a head), Mamba-1's at the end of
the file (a decay a channel and state).

A head ``h`` carries a matrix ``S`` of ``P x N`` (head size by state size).
With ``dt_t >= 0`` the step size of position ``t``, ``A < 0`` the head's
decay rate, ``x_t`` (P,) the head's input and ``B_t``, ``C_t`` (N,) the
vectors of the head's group (``G`` groups share ``B`` and ``C`` over ``H // G``
heads each):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          y_t = S_t C_t

``D x_t``, the gate and the normalisation are the caller's. A position with
``dt_t == 0`` leaves the state exactly as it was (``exp(0) == 1`` and
nothing is added), which is how padding positions and idle rows are told
apart from real ones: the caller zeroes their ``dt``.

:func:`ssm_chunked` is the chunked form (Dao and Gu, 2024, "SSD"): inside
a chunk of ``L`` positions every output is a masked ``L x L`` product over
the chunk's own inputs plus the carried state read through ``C``, and the
state moves once a chunk; a longer run is a ``lax.scan`` over chunks. The
products take their operands in ``x``'s dtype and accumulate in float32;
decays, the state and what is carried between chunks are float32 whatever
``x`` is: the state is a sum over every position a sequence has had.
:func:`ssm_step` is the recurrence itself for one position, elementwise
over the state, bound by the state's bytes (read once, written once).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _heads(v, heads: int):
    """``(..., G, N)`` -> ``(..., H, N)``: head ``h`` reads group ``h // (H
    // G)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def _one_chunk(state, x, dt, a, b, c):
    """One chunk from ``state`` (B, H, P, N) float32: ``x`` (B, L, H, P),
    ``dt`` (B, L, H) float32, ``a`` (H,) float32, ``b``/``c`` (B, L, G, N).
    Returns the state after the chunk and ``y`` (B, L, H, P) float32."""
    heads, dtype = x.shape[2], x.dtype
    L = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)  # (B, L, H): log decay up to t
    # within the chunk: y_t += sum_{s <= t} exp(cum_t - cum_s) dt_s
    # (C_t . B_s) x_s; the mask goes in before the exponential (above the
    # diagonal cum_t - cum_s is positive and may overflow)
    scores = jnp.einsum("blgn,bsgn->bgls", c, b,
                        preferred_element_type=F32)  # (B, G, L, L)
    scores = jnp.repeat(scores, heads // scores.shape[1], axis=1)
    by_head = jnp.swapaxes(cum, 1, 2)  # (B, H, L)
    diff = by_head[:, :, :, None] - by_head[:, :, None, :]  # (B, H, t, s)
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    mixed = scores * decay * jnp.swapaxes(dt, 1, 2)[:, :, None, :]
    y = jnp.einsum("bhls,bshp->blhp", mixed.astype(dtype), x,
                   preferred_element_type=F32)
    # the carried state, read through C and decayed up to t
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "blhn,bhpn->blhp", _heads(c, heads).astype(F32), state,
        precision=lax.Precision.HIGHEST)
    # the state after the chunk: what was carried, decayed over the whole
    # chunk, and every position's outer product decayed from there on
    last = cum[:, -1]  # (B, H)
    weight = jnp.exp(last[:, None] - cum) * dt  # (B, L, H)
    added = jnp.einsum(
        "blhp,blhn->bhpn", (x.astype(F32) * weight[..., None]).astype(dtype),
        _heads(b, heads), preferred_element_type=F32)
    return jnp.exp(last)[..., None, None] * state + added, y


def ssm_chunked(x, dt, a, b, c, state, *, chunk: int = 128):
    """``S`` positions of ``B`` sequences from ``state`` (B, H, P, N)
    float32 (zeros for a sequence's first): ``x`` (B, S, H, P), ``dt`` (B,
    S, H) float32 (0 at positions that are padding), ``a`` (H,) float32,
    ``b`` and ``c`` (B, S, G, N). Returns ``(y (B, S, H, P) float32, the
    state after the last position)``. ``S`` above ``chunk`` is a scan over
    chunks of ``chunk`` positions (the run padded to whole chunks with ``dt
    == 0``, which moves nothing)."""
    S = x.shape[1]
    state, dt, a = state.astype(F32), dt.astype(F32), a.astype(F32)
    if S <= chunk:
        state, y = _one_chunk(state, x, dt, a, b, c)
        return y, state
    n = -(-S // chunk)

    def split(v):  # (B, S, ...) -> (n, B, chunk, ...)
        v = jnp.pad(v, ((0, 0), (0, n * chunk - S)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(
            v.reshape(v.shape[0], n, chunk, *v.shape[2:]), 1, 0)

    state, ys = lax.scan(lambda s, xs: _one_chunk(s, *xs[:2], a, *xs[2:]),
                         state, (split(x), split(dt), split(b), split(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(x.shape[0], n * chunk, *x.shape[2:])
    return y[:, :S], state


def ssm_step(x, dt, a, b, c, state):
    """One position of every row: ``x`` (B, H, P), ``dt`` (B, H) float32 (0
    for a row that is idle), ``a`` (H,), ``b`` and ``c`` (B, G, N),
    ``state`` (B, H, P, N) float32. Returns ``(y (B, H, P) float32, the
    state after)``: the recurrence as written, elementwise over the state,
    so that a compiler fuses read, update, read-out and write into one pass
    over the state's bytes."""
    heads = x.shape[1]
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))[..., None, None]
    drive = (dt[..., None] * x.astype(F32))[..., None]  # (B, H, P, 1)
    state = (decay * state.astype(F32)
             + drive * _heads(b, heads).astype(F32)[:, :, None, :])
    y = jnp.sum(state * _heads(c, heads).astype(F32)[:, :, None, :], axis=-1)
    return y, state


# --------------------------------------------------------------------------
# Mamba-1: a decay a channel and state
# --------------------------------------------------------------------------
#
# A channel ``c`` of ``D`` carries a vector ``h[c, :]`` of ``N`` numbers, and
# the decay is the channel's and the state's own, ``A`` (D, N) (Mamba-2's is
# one number a head, which is what lets :func:`_one_chunk` turn a chunk into
# matrix products; here there is no such factoring and the recurrence runs a
# position at a time):
#
#     h_t[c, :] = exp(dt_t[c] A[c, :]) h_{t-1}[c, :] + dt_t[c] u_t[c] B_t
#     y_t[c]    = h_t[c, :] . C_t
#
# ``B_t`` and ``C_t`` (N,) are shared by every channel. ``D u_t``, the gate
# and the output projection are the caller's; ``dt_t == 0`` leaves the state
# as it was, as above.


def selective_step(u, dt, a, b, c, state):
    """One position of every row: ``u`` (B, D), ``dt`` (B, D) float32 (0
    for a row that is idle), ``a`` (D, N) float32, ``b`` and ``c`` (B, N),
    ``state`` (B, D, N) float32. Returns ``(y (B, D) float32, the state
    after)``, elementwise over the state: one pass over its bytes."""
    dt = dt.astype(F32)
    decay = jnp.exp(dt[..., None] * a.astype(F32))
    drive = (dt * u.astype(F32))[..., None] * b.astype(F32)[:, None, :]
    state = decay * state.astype(F32) + drive
    return jnp.sum(state * c.astype(F32)[:, None, :], axis=-1), state


def selective_scan(u, dt, a, b, c, state):
    """``S`` positions of ``B`` sequences from ``state`` (B, D, N) float32
    (zeros for a sequence's first): ``u`` (B, S, D), ``dt`` (B, S, D)
    float32 (0 at positions that are padding), ``a`` (D, N), ``b`` and
    ``c`` (B, S, N). Returns ``(y (B, S, D) float32, the state after the
    last position)``: :func:`selective_step` over the positions in turn, a
    ``lax.scan`` whose carry is the state."""
    def step(state, at):
        y, state = selective_step(*at[:2], a, *at[2:], state)
        return state, y

    by_position = tuple(jnp.swapaxes(v, 0, 1) for v in (u, dt, b, c))
    state, ys = lax.scan(step, state.astype(F32), by_position)
    return jnp.swapaxes(ys, 0, 1), state
