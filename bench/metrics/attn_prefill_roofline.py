"""Share of its roofline that the flash kernel reaches inside the
prefill_into program: the traced chunks' attention work (valid queries
over their actual context) at peak, over the kernel's device time."""

from bench import work
from bench.readers import roofline_share, traced_calls


def read(run):
    d = run.dims

    def per_call(c):
        return work.attention_chunk(c[2], c[3], heads=d["heads"], kv_heads=d["kv_heads"],
                                    head_dim=d["head_dim"])

    return roofline_share(run, traced_calls(run.rec, run.rec.prefill), "prefill_into",
                          "flash_attention", per_call, d["attn_layers"])
