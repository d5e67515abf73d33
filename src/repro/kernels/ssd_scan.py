"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD blocking: the grid is (batch, heads, chunks)
with chunks minor (sequential), and the inter-chunk SSM state (N, P) lives
in fp32 VMEM scratch carried across chunk steps — the recurrence never
round-trips HBM.  Per chunk the kernel computes the intra-chunk "dual"
attention block (Q x Q masked matmul -> MXU) and the state in/out terms,
exactly mirroring ssd_scan_ref's math.

Block shapes: chunk Q defaults to 128 (MXU aligned); VMEM per step is
O(Q*P + Q*N + Q*Q + N*P) fp32 — ~0.5 MB for Q=128, P=64, N=128.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tuning.config import BlockConfig, default_config

__all__ = ["ssd_scan"]

_DEFAULTS = default_config("ssd_scan")   # single source of truth for fallbacks


def _ssd_kernel(
    x_ref,       # (1, 1, 1, Q, P)
    row_ref,     # (1, 1, 1, 2, Q)  [dt; cumsum(dt*A)] as rows
    col_ref,     # (1, 1, 1, Q, 2)  [dt | cumsum(dt*A)] as columns
    b_ref,       # (1, 1, 1, Q, N)
    c_ref,       # (1, 1, 1, Q, N)
    y_ref,       # (1, 1, 1, Q, P)
    st_ref,      # (1, 1, N, P)   final state (last write wins)
    state_ref,   # scratch (N, P) fp32
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    bm = b_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)
    # dt and the inclusive log-decay cumsum (computed by the wrapper)
    # arrive as 2-D rows and columns: Mosaic cannot tile a (Q,) vector
    dt_row = row_ref[0, 0, 0, 0:1, :]               # (1, Q)
    cum_row = row_ref[0, 0, 0, 1:2, :]              # (1, Q)
    dt_col = col_ref[0, 0, 0, :, 0:1]               # (Q, 1)
    cum_col = col_ref[0, 0, 0, :, 1:2]              # (Q, 1)

    # intra-chunk dual form
    diff = cum_col - cum_row                        # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(qi >= kj, diff, -jnp.inf))  # mask pre-exp
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, Q)
    m = scores * decay * dt_row
    y = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, P)

    # contribution of the carried state
    state = state_ref[...]                          # (N, P)
    c_decay = cm * jnp.exp(cum_col)
    y = y + jax.lax.dot_general(
        c_decay, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update: decay to end of chunk
    # the chunk's total log decay as a 0-d value: a (1, 1) slice at lane
    # Q-1 cannot be broadcast over sublanes and lanes at once
    last = jnp.max(cum_row[:, chunk - 1:chunk])     # ()
    decay_to_end = jnp.exp(last - cum_col)          # (Q, 1)
    wb = bm * (decay_to_end * dt_col)               # (Q, N)
    new_state = state * jnp.exp(last) + jax.lax.dot_general(
        wb, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (N, P)
    state_ref[...] = new_state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = new_state                        # last chunk's write survives


@functools.partial(jax.jit, static_argnames=("chunk", "config", "interpret"))
def ssd_scan(
    x: jnp.ndarray,     # (B, S, H, P)
    dt: jnp.ndarray,    # (B, S, H)
    A: jnp.ndarray,     # (H,)
    Bm: jnp.ndarray,    # (B, S, G, N)
    Cm: jnp.ndarray,    # (B, S, G, N)
    *,
    chunk: int | None = None,
    config: BlockConfig | None = None,
    interpret: bool = False,
):
    b, s, h, p = x.shape
    if chunk is None:
        cfg = config if config is not None else _DEFAULTS
        chunk = min(cfg.get("chunk", _DEFAULTS["chunk"]), s)
        if s % chunk:
            # a tuned/default tile that doesn't divide this sequence degrades
            # to the largest common divisor instead of tripping the assert
            chunk = math.gcd(chunk, s)
    g, n = Bm.shape[2], Bm.shape[3]
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    nc = s // chunk
    heads_per_group = h // g

    # (B, H, NC, Q, ...) layouts so the chunk axis is a clean grid dim
    xr = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtr = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(b, h, nc, chunk)
    # per-chunk inclusive cumsum of the log decay dt*A (A folds in here)
    cum = jnp.cumsum(dtr * A.astype(jnp.float32)[None, :, None, None], axis=-1)
    rows = jnp.stack([dtr, cum], axis=3)            # (b, h, nc, 2, chunk)
    cols = jnp.stack([dtr, cum], axis=4)            # (b, h, nc, chunk, 2)
    br = Bm.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)
    cr = Cm.transpose(0, 2, 1, 3).reshape(b, g, nc, chunk, n)

    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 2, chunk), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 2), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec(
                (1, 1, 1, chunk, n),
                lambda bi, hi, ci: (bi, hi // heads_per_group, ci, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, chunk, n),
                lambda bi, hi, ci: (bi, hi // heads_per_group, ci, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xr, rows, cols, br, cr)

    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, st
