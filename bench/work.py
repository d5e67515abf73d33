"""Operations and bytes that each kernel call needs, from its shapes.

The count is the work of the algorithm, whatever implements it: valid
tokens only (never a chunk's padding), attention over each row's actual
context (never the cache's full extent), active decode rows only (never
parked ones), every operand read once and every result written once.
Waste in an implementation therefore shows as a lower share of the
roofline, and no share can pass 100% through a miscount.

A multiply-add counts as two operations.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Work", "attention_chunk", "attention_decode", "ssd_scan",
           "roofline_time"]

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)


ZERO = Work(0.0, 0.0)


def attention_chunk(n: int, pos: int, *, heads: int, kv_heads: int,
                    head_dim: int, itemsize: int = BF16) -> Work:
    """One layer's causal attention of `n` queries at positions
    pos..pos+n-1 over keys 0..pos+n-1 (chunked prefill)."""
    pairs = n * pos + n * (n + 1) // 2          # (query, key) pairs attended
    flops = 4.0 * heads * head_dim * pairs      # q.k and p.v
    q_o = 2 * n * heads * head_dim              # queries in, outputs out
    k_v = 2 * (pos + n) * kv_heads * head_dim   # keys and values, once
    return Work(flops, float(itemsize * (q_o + k_v)))


def attention_decode(contexts, *, heads: int, kv_heads: int, head_dim: int,
                     itemsize: int = BF16) -> Work:
    """One layer's decode attention for the active rows; `contexts` holds
    each active row's number of keys (its position + 1)."""
    keys = sum(int(c) for c in contexts)
    rows = len(contexts)
    flops = 4.0 * heads * head_dim * keys
    q_o = 2 * rows * heads * head_dim
    k_v = 2 * keys * kv_heads * head_dim
    return Work(flops, float(itemsize * (q_o + k_v)))


def ssd_scan(n: int, *, heads: int, head_dim: int, state: int,
             groups: int = 1, itemsize: int = BF16) -> Work:
    """One layer's SSD scan over `n` valid tokens from a zero state.

    The recurrence h_t = exp(dt A) h_{t-1} + dt x_t B_t^T, y_t = C_t h_t
    needs three operations per (token, head, head_dim, state) element for
    the update and two for the read-out.  Bytes: x and y in the served
    dtype, dt in fp32, B and C once per group, and the final fp32 state.
    """
    flops = 5.0 * n * heads * head_dim * state
    nbytes = (itemsize * 2 * n * heads * head_dim      # x in, y out
              + F32 * n * heads                        # dt
              + itemsize * 2 * n * groups * state      # B and C
              + F32 * heads * state * head_dim)        # final state
    return Work(flops, float(nbytes))


def roofline_time(work: Work, peaks) -> tuple[float, str]:
    """Least time the chip could take for `work`, and which bound it is."""
    t_compute = work.flops / peaks.flops_bf16
    t_memory = work.bytes / peaks.hbm_bandwidth
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
