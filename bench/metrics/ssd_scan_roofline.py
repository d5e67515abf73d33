"""Share of its roofline that the SSD scan kernel reaches inside the
prefill_into program: the traced chunks' scan work over their valid
tokens at peak, over the kernel's device time."""

from bench import work
from bench.readers import roofline_share, traced_calls


def read(run):
    d = run.dims

    def per_call(c):
        return work.ssd_scan(c[2], heads=d["ssm_heads"], head_dim=d["ssm_head_dim"],
                             state=d["ssm_state"], groups=d["ssm_groups"])

    return roofline_share(run, traced_calls(run.rec, run.rec.prefill), "prefill_into",
                          "ssd_scan", per_call, d["ssm_layers"])
