"""FLOPs one round of ``resnet56_cifar`` needs, from shapes alone.

Per sample, forward: every convolution costs 2 * k*k * C_in * C_out * H_out *
W_out and the dense layer 2 * C * classes; normalisation, ReLU and pooling are
not counted. Forward and backward together are 3x the forward (the input
gradient and the weight gradient each cost one forward). A round trains every
real sample of every client once per local epoch; padding rows do not count.
"""


def forward_flops_per_sample(cfg):
    h, w, c_in = cfg["input_shape"]
    total = 2 * 9 * c_in * cfg["stem_channels"] * h * w
    c_in = cfg["stem_channels"]
    for stage, c_out in enumerate(cfg["stage_channels"]):
        for block in range(cfg["blocks_per_stage"]):
            stride = 2 if (stage > 0 and block == 0) else 1
            h, w = h // stride, w // stride
            total += 2 * 9 * c_in * c_out * h * w      # first 3x3
            total += 2 * 9 * c_out * c_out * h * w     # second 3x3
            if stride != 1 or c_in != c_out:
                total += 2 * c_in * c_out * h * w      # 1x1 projection
            c_in = c_out
    return total + 2 * c_in * cfg["num_classes"]


def flops_per_round(cfg, traffic):
    return (3.0 * forward_flops_per_sample(cfg) * traffic["samples_total"]
            * traffic["local_epochs"])
