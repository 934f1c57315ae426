"""The work one rank does in one epoch of the imaging32 configuration,
from its widths, as the GAN algorithm requires it, whatever implements it.

The generator is a dense projection of the noise to a base x base x c0
grid, then per stage a nearest x2 upsample (no arithmetic) and a 3x3
convolution, and a last 3x3 convolution to one channel.  A 3x3
convolution costs H * W * 9 * cin * cout multiply-accumulates.  The
discriminator step, the generator step through the discriminator and the
generator passes are counted as for the MLP configuration:
3 x d_fwd x 2B + 2 x d_fwd x B + 4 x g_fwd x K, at 2 FLOP per MAC.
"""


def _mlp_macs(widths):
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def _conv_gen_macs(g):
    base, ch = g["base"], g["channels"]
    macs = g["noise_dim"] * base * base * ch[0]
    side = base
    outs = ch[1:] + [1]
    for i, (cin, cout) in enumerate(zip(ch, outs)):
        if i < len(ch) - 1:
            side *= 2
        macs += side * side * 9 * cin * cout
    return macs


def flops_per_rank_epoch(cfg) -> float:
    K, E = cfg["n_param_samples"], cfg["events_per_sample"]
    B = K * E
    d_fwd = 2.0 * _mlp_macs(cfg["discriminator"]["widths"])
    g_fwd = 2.0 * _conv_gen_macs(cfg["generator"])
    return 3 * d_fwd * 2 * B + 2 * d_fwd * B + 4 * g_fwd * K
