"""VGG-16 (Simonyan & Zisserman 2015, configuration D): thirteen 3x3
convs in five blocks (64, 128, 256, 512, 512 channels), each block
followed by a 2x2 max-pool, then the classifier: three FC layers on
ImageNet (4096, 4096, 1000), two on CIFAR (512, classes)."""

from bench.reference.families import conv, gemm, table


def build(dataset: str = "imagenet", batch: int = 1) -> dict:
    if dataset == "imagenet":
        h, fc = 224, [4096, 4096, 1000]
    else:
        h, fc = 32, [512, 100 if dataset == "cifar100" else 10]
    rows, c = [], 3
    for k, reps in ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3)):
        for _ in range(reps):
            rows.append(conv(h, h, c, k, 3, batch=batch))
            c = k
        h //= 2
    width = h * h * c
    for n in fc:
        rows.append(gemm(1, width, n, batch=batch))
        width = n
    return table(f"vgg16-{dataset}", rows)
