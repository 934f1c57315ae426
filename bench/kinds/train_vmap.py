"""Traffic kind `train_vmap`: R = n_outer x n_inner ranks vmapped on one
chip.  Each call runs `epochs_per_call` epochs of the compiled chunk of
`workflow.make_chunk_runner`, as `train_vmap` does, on the carried state.

    n_outer, n_inner    the outer and inner rings' sizes
    epochs_per_call     epochs in one compiled chunk
    checked_calls, in_flight, trace_seconds: see harness/train.py
"""
from harness import train


def build(traffic, wcfg):
    from repro.core import workflow
    n_outer, n_inner = traffic["n_outer"], traffic["n_inner"]
    runner = workflow.make_chunk_runner(n_outer, n_inner, wcfg)
    n = traffic["epochs_per_call"]
    return train.Entry(call=lambda s, d: runner(s, d, n), epochs_per_call=n,
                       n_outer=n_outer, n_inner=n_inner, shardings=None)


def run(ctx):
    return train.run(ctx, build)
