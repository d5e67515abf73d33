"""Tables III/IV analogue — OSU latency: native vs container collectives.

The paper pits the host's vendor MPI (Aries / InfiniBand) against the
container's generic MPI across message sizes.  Here the logical
`grad_allreduce` collective has a reference schedule (flat all-reduce over
all DP axes — the bundle's portable implementation) and a native schedule
(hierarchical: ICI reduce-scatter -> DCN all-reduce on 1/N shards -> ICI
all-gather), plus the int8-compressed DCN variant.  For every message
size we report measured wall-clock on the 8-virtual-device host AND the
structural DCN bytes per device (the quantity the real fabric feels);
derived shows numerics parity (max |err|) — the paper's "ratio = 1.0"
claim — and the DCN byte reduction.
"""

from __future__ import annotations

import json

from benchmarks.common import row, run_subprocess

_SIZES = [32, 128, 512, 2048, 8192, 32768, 131072, 524288, 2097152]

_CODE = f"""
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed import flat_grad_allreduce, hierarchical_grad_allreduce

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
results = []
for size_bytes in {_SIZES!r}:
    n = max(size_bytes // 4, 1)
    x = {{"g": jnp.arange(n, dtype=jnp.float32) / n}}

    def run_fn(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(),),
                                     out_specs=P(), check_vma=False))

    flat = run_fn(lambda t: flat_grad_allreduce(t, data_axis="data", pod_axis="pod"))
    hier = run_fn(lambda t: hierarchical_grad_allreduce(t, data_axis="data", pod_axis="pod"))
    comp = run_fn(lambda t: hierarchical_grad_allreduce(
        t, data_axis="data", pod_axis="pod", compress_dcn=True))

    out_f = flat(x)["g"]; out_h = hier(x)["g"]; out_c = comp(x)["g"]
    err_h = float(jnp.abs(out_f - out_h).max())
    err_c = float(jnp.abs(out_f - out_c).max())

    def med(f):
        f(x)["g"].block_until_ready()
        ts = []
        for _ in range(5):
            t0 = time.perf_counter(); f(x)["g"].block_until_ready()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[2]

    # structural DCN bytes per device (the thin-pipe cost the schedule moves)
    dcn_flat = size_bytes                # whole tensor crosses pods
    dcn_hier = size_bytes // 4           # 1/data_size shard crosses pods
    dcn_comp = dcn_hier // 4             # int8 + scale vs f32

    results.append(dict(size=size_bytes,
                        t_flat=med(flat), t_hier=med(hier), t_comp=med(comp),
                        err_h=err_h, err_c=err_c,
                        dcn_flat=dcn_flat, dcn_hier=dcn_hier, dcn_comp=dcn_comp))
print(json.dumps(results))
"""


def run() -> list[tuple[str, float, str]]:
    out = run_subprocess(_CODE, devices=8)
    results = json.loads(out.strip().splitlines()[-1])
    rows = []
    for r in results:
        rows.append(row(
            f"table34/allreduce_flat/{r['size']}B",
            r["t_flat"] * 1e6,
            f"dcn_bytes={r['dcn_flat']}",
        ))
        rows.append(row(
            f"table34/allreduce_hier/{r['size']}B",
            r["t_hier"] * 1e6,
            f"dcn_bytes={r['dcn_hier']};err_vs_flat={r['err_h']:.1e}",
        ))
        rows.append(row(
            f"table34/allreduce_int8dcn/{r['size']}B",
            r["t_comp"] * 1e6,
            f"dcn_bytes={r['dcn_comp']};err_vs_flat={r['err_c']:.1e}",
        ))
    return rows
