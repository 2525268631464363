"""Model zoo: the 10 assigned architectures + the paper's CNNs.

``family_module(cfg)`` dispatches an ArchConfig to its implementation:
  lm / vlm / moe -> transformer (decoder-only, scan-over-layers)
  ssm            -> rwkv (RWKV6 chunked linear attention)
  hybrid         -> hybrid (zamba2: Mamba2 + shared attention blocks)
  encdec         -> encdec (whisper-style)
"""

from repro.models import (cnn, encdec, hybrid, layers, mamba, mla, moe,
                          rwkv, ssm_common, transformer)


def family_module(cfg):
    fam = cfg.family
    if fam in ("lm", "vlm", "moe"):
        return transformer
    if fam == "ssm":
        return rwkv
    if fam == "hybrid":
        return hybrid
    if fam == "encdec":
        return encdec
    raise ValueError(f"unknown family {fam}")


__all__ = ["cnn", "encdec", "hybrid", "layers", "mamba", "mla", "moe",
           "rwkv", "ssm_common", "transformer", "family_module"]
