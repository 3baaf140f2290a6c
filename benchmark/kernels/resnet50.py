"""Operations ResNet-50 requires per trained image: forward plus backward
(three times the forward's), counted layer by layer from the architecture
table (2 x multiply-accumulates of every convolution and of the linear
layer). Batch norm, pooling and ReLU are left out."""

STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _same(size, stride):
    return -(-size // stride)


def forward_macs(height, width, channels, n_classes):
    h, w = _same(height, 2), _same(width, 2)
    macs = h * w * 7 * 7 * channels * 64
    h, w = _same(h, 2), _same(w, 2)
    c_in = 64
    for f, blocks, stride in STAGES:
        for b in range(blocks):
            s = stride if b == 0 else 1
            ho, wo = _same(h, s), _same(w, s)
            macs += ho * wo * c_in * f              # 1x1 reduce (strided)
            macs += ho * wo * 9 * f * f             # 3x3
            macs += ho * wo * f * 4 * f             # 1x1 expand
            if b == 0:
                macs += ho * wo * c_in * 4 * f      # projection shortcut
            h, w, c_in = ho, wo, 4 * f
    return macs + c_in * n_classes


def train_flops_per_unit(model, traffic):
    return 3 * 2 * forward_macs(model["height"], model["width"],
                                model["channels"], model["n_classes"])
