"""State-space duality (Mamba-2): the selective scan computed in chunks.

The recurrence, for one head with state ``S`` of ``[P, N]``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

is linear in ``S``, so a chunk of ``L`` positions splits into what the chunk's
own inputs add (a ``[L, L]`` product, masked by the decay between the two
positions: attention-shaped work for the MXU) and what the state at the
chunk's start adds (one product with ``S``).  Only the chunk states pass from
chunk to chunk, ``T / L`` sequential steps instead of ``T``.

Plain ``jax.numpy`` / ``lax``: the contractions take ``compute_dtype``
operands and accumulate in float32; the decays (cumulative sums of ``dt * a``
and their exponentials) and the passed state stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution along ``T``: ``x`` ``[B, T, C]``,
    ``weight`` ``[K, C]`` (tap ``K - 1`` reads the position itself), ``bias``
    ``[C]``.  Positions before the first read as zero."""
    with jax.named_scope("conv1d"):
        taps, t = weight.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return sum(padded[:, k:k + t] * weight[k] for k in range(taps)) + bias


def ssd_scan(x, dt, a, b, c, *, chunk: int, compute_dtype=jnp.bfloat16,
             initial_state=None, return_state: bool = False):
    """``y`` ``[B, T, H, P]`` float32 of the recurrence above (without the
    ``D * x`` skip).

    ``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]``, positive (after the
    softplus); ``a`` ``[H]``, negative; ``b``, ``c`` ``[B, T, G, N]``, head
    ``j`` reading group ``j // (H / G)``.  ``T`` need not be a whole number of
    chunks.  ``initial_state`` ``[B, H, P, N]`` is the state before the first
    position (zero by default); ``return_state`` also returns the state after
    the last."""
    with jax.named_scope("ssd_scan"):
        bsz, t, heads, p = x.shape
        groups, n = b.shape[2:]
        per = heads // groups
        f32 = jnp.float32
        exact = lax.Precision.HIGHEST if jnp.dtype(compute_dtype) == f32 else None
        pad = -t % chunk
        if pad:
            # dt = 0 past the end: the state neither decays nor takes anything in.
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                           for v in (x, dt, b, c))
        nc = (t + pad) // chunk
        dt = dt.astype(f32)
        # [B, nc, G, J, L]: the log-decay of each step and its running sum.
        log_decay = (dt * a.astype(f32)).reshape(bsz, nc, chunk, groups, per).transpose(0, 1, 3, 4, 2)
        cum = jnp.cumsum(log_decay, axis=-1)
        xdt = (x.astype(f32) * dt[..., None]).reshape(bsz, nc, chunk, groups, per, p)
        bc = b.reshape(bsz, nc, chunk, groups, n).astype(compute_dtype)
        cc = c.reshape(bsz, nc, chunk, groups, n).astype(compute_dtype)

        # Within a chunk: (C B^T masked by the decay from s to l) x.
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, precision=exact, preferred_element_type=f32)
        seg = cum[..., :, None] - cum[..., None, :]  # [B, nc, G, J, L(l), L(s)]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        scores = cb[:, :, :, None] * jnp.exp(jnp.where(lower, seg, -jnp.inf))
        y = jnp.einsum("bcgjls,bcsgjp->bclgjp", scores.astype(compute_dtype),
                       xdt.astype(compute_dtype), precision=exact, preferred_element_type=f32)

        # What each chunk's inputs leave in the state at the chunk's end.
        to_end = jnp.exp(cum[..., -1:] - cum)  # [B, nc, G, J, L]
        left = jnp.einsum("bclgjp,bclgn->bcgjpn",
                          (xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(compute_dtype),
                          bc, precision=exact, preferred_element_type=f32)

        # From chunk to chunk: the state at each chunk's start.
        first = (jnp.zeros((bsz, groups, per, p, n), f32) if initial_state is None
                 else initial_state.astype(f32).reshape(bsz, groups, per, p, n))
        chunk_decay = jnp.exp(cum[..., -1])  # [B, nc, G, J]

        def pass_on(state, at):
            decay, added = at
            return decay[..., None, None] * state + added, state

        last, starts = lax.scan(pass_on, first, (chunk_decay.swapaxes(0, 1), left.swapaxes(0, 1)))
        starts = starts.swapaxes(0, 1)  # [B, nc, G, J, P, N]

        # What the state at the chunk's start adds at each position.
        y = y + jnp.einsum("bclgn,bcgjpn->bclgjp", cc, starts.astype(compute_dtype),
                           precision=exact, preferred_element_type=f32) \
            * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
        y = y.reshape(bsz, nc * chunk, heads, p)[:, :t]
        if return_state:
            return y, last.reshape(bsz, heads, p, n)
        return y
