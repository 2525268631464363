"""ResNet for CIFAR (He et al. 2016, Sec. 4.2): a 3x3 stem of 16
channels, three stages of n = (depth - 2) / 6 basic blocks at 16, 32
and 64 channels, the first block of stages 2 and 3 striding by 2 with a
1x1 projection shortcut, then the classifier."""

from bench.reference.families import conv, gemm, table


def build(depth: int, dataset: str = "cifar10", batch: int = 1) -> dict:
    n = (depth - 2) // 6
    classes = 100 if dataset == "cifar100" else 10
    h, c = 32, 16
    rows = [conv(h, h, 3, 16, 3, batch=batch)]
    for stage, k in enumerate((16, 32, 64)):
        for b in range(n):
            s = 2 if stage > 0 and b == 0 else 1
            rows.append(conv(h, h, c, k, 3, stride=s, batch=batch))
            rows.append(conv(h // s, h // s, k, k, 3, batch=batch))
            if s == 2 or c != k:
                rows.append(conv(h, h, c, k, 1, stride=s, batch=batch))
            h, c = h // s, k
    rows.append(gemm(1, 64, classes, batch=batch))
    return table(f"resnet{depth}-{dataset}", rows)
