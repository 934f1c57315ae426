"""Traffic kind `train_shard`: one rank per chip on a (pod, data) mesh.
Each call is one epoch of `workflow.make_epoch_fn_shard` on the carried,
sharded state.

    mesh                {"pod": n_outer, "data": n_inner}
    checked_calls, in_flight, trace_seconds: see harness/train.py
"""
from harness import train


def build(traffic, wcfg):
    from repro.core import workflow
    from repro.launch.mesh import make_mesh
    n_outer, n_inner = traffic["mesh"]["pod"], traffic["mesh"]["data"]
    mesh = make_mesh((n_outer, n_inner), ("pod", "data"))
    fn, shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
    return train.Entry(call=fn, epochs_per_call=1, n_outer=n_outer,
                       n_inner=n_inner, shardings=shardings)


def run(ctx):
    return train.run(ctx, build)
