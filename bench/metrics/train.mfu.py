"""train.mfu: the FLOPs the GAN algorithm requires (bench/counts, from
the configuration's widths) for the rank-epochs the traced window
completed, over the window's host-clock length, the cell's chips and one
chip's bf16 peak (bench/peaks.json), in %."""


def read(run):
    rank_epochs = run.facts.get("rank_epochs")
    if not rank_epochs or not run.window_s or run.peaks is None:
        return None
    flops = run.facts["flops_per_rank_epoch"] * rank_epochs
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops / run.window_s / peak
