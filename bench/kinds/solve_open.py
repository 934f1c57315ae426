"""Traffic kind `solve_open`: independent clients of `SolveService`, in
an open loop; see harness/solve.py for the parameters it reads."""
from harness import solve


def run(ctx):
    return solve.run(ctx)
