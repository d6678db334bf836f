"""Model FLOP/s of the whole P2P step as a share of the chips' bf16 peak.

FLOPs per sample are 3 forward passes' worth (forward, and backward at
twice the forward), 2 per multiply-accumulate of the configuration's
convolutions and products (``layer_macs`` of its reference); biases,
norms, pools and activations are not counted. The program computes its
float32 products at XLA's default precision, one bf16 pass on a TPU, so
the bf16 peak bounds it."""


def read(run):
    macs = sum(run.cell.model.layer_macs(run.cell.config).values())
    flops = run.samples * 6 * macs
    return 100 * flops / (run.window_s * run.chips * run.peaks()["flops_bf16"])
