"""ResNet-34 and ResNet-50 for ImageNet (He et al. 2016, Table 1): a
7x7 stride-2 stem on a 224 image (3 pixels of padding each side), a
max-pool to 56x56, four stages of 3/4/6/3 blocks at 64/128/256/512
channels (basic blocks for 34, 1x1-3x3-1x1 bottlenecks with 4x
expansion for 50), the first block of stages 2-4 striding by 2, a 1x1
projection shortcut wherever the channel count changes, then a
1000-way FC."""

from bench.reference.families import conv, gemm, table


def build(depth: int, batch: int = 1) -> dict:
    rows = [conv(230, 230, 3, 64, 7, stride=2, batch=batch, valid=True)]
    h, c = 56, 64
    bottleneck = depth == 50
    for stage, (k, reps) in enumerate(((64, 3), (128, 4), (256, 6),
                                       (512, 3))):
        out = 4 * k if bottleneck else k
        for b in range(reps):
            s = 2 if stage > 0 and b == 0 else 1
            if bottleneck:
                rows.append(conv(h, h, c, k, 1, batch=batch))
                rows.append(conv(h, h, k, k, 3, stride=s, batch=batch))
                rows.append(conv(h // s, h // s, k, out, 1, batch=batch))
            else:
                rows.append(conv(h, h, c, k, 3, stride=s, batch=batch))
                rows.append(conv(h // s, h // s, k, k, 3, batch=batch))
            if c != out:
                rows.append(conv(h, h, c, out, 1, stride=s, batch=batch))
            h, c = h // s, out
    rows.append(gemm(1, c, 1000, batch=batch))
    return table(f"resnet{depth}-imagenet", rows)
