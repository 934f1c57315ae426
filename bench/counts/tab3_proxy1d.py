"""The work one rank does in one epoch of the paper's Tab. III
configuration, from its published widths (arXiv 2407.00051, §V-A), as the
GAN algorithm requires it, whatever implements it.

Per rank and epoch, with K parameter samples of E events (B = K * E):
  discriminator step   forward on B real and B fake events, backward for
                       weights and activations: 3 x fwd x 2B
  generator step       forward of the fake events through the
                       discriminator and back to its input: 2 x fwd x B
  generator            forward for the discriminator step, and forward
                       and backward for the generator step: 4 x fwd x K
A forward pass costs 2 FLOP per multiply-accumulate of its dense layers.
"""


def _mlp_macs(widths):
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def flops_per_rank_epoch(cfg) -> float:
    K, E = cfg["n_param_samples"], cfg["events_per_sample"]
    B = K * E
    d_fwd = 2.0 * _mlp_macs(cfg["discriminator"]["widths"])
    g_fwd = 2.0 * _mlp_macs(cfg["generator"]["widths"])
    return 3 * d_fwd * 2 * B + 2 * d_fwd * B + 4 * g_fwd * K
