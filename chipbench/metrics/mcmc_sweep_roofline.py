"""Share of the HBM roofline reached by the `mcmc_sweep` kernel on the
streamed bit-plane tier, in percent: the bytes the algorithm must read —
one coupling row of all bit-planes (2·B·N/8 bytes) for every flip, from the
program's flip counter — over the peak HBM bandwidth, divided by the
kernel's device time. Each step flips at most one spin per replica and two
replicas rarely pick the same row, so this counts each row a flip needs
once; the state stays in VMEM and is not counted."""
from chipbench import hbm


def read(run):
    if run.trace is None:
        return None
    return hbm.roofline_share(run, "mcmc_sweep",
                              hbm.flips(run) * run.row_bytes)
