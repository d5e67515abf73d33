"""Share of its roofline that the flash kernel reaches inside the decode
program: the traced steps' attention work (active rows over their
actual context, never parked rows) at peak, over the kernel's device
time."""

from bench import work
from bench.readers import roofline_share, traced_calls


def read(run):
    d = run.dims

    def per_call(c):
        return work.attention_decode(c[2], heads=d["heads"], kv_heads=d["kv_heads"],
                                     head_dim=d["head_dim"])

    return roofline_share(run, traced_calls(run.rec, run.rec.decode), "decode",
                          "flash_attention", per_call, d["attn_layers"])
