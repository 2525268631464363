"""Figs. 5-6: accuracy x perf/area and accuracy x energy Pareto fronts.

The paper trains VGG-16 / ResNet-20 / ResNet-56 under each PE type's
numerics (5 trials, SGD-nesterov recipe) and plots mean top-1 accuracy vs
the best-perf/area (Fig. 5) / lowest-energy (Fig. 6) hardware config of
that PE type.  Claims: LightPEs sit ON the Pareto front; accuracy on par
(gap shrinks with model size); LightPE-1 up to 5.7x perf/area vs INT16.

This bench trains small ResNets on the CIFAR-like synthetic set (DESIGN.md
§6) for a fixed budget per PE type (fast CPU-scale stand-in for the
200-epoch recipe; examples/train_qat.py runs the longer version) and joins
with the DSE hardware numbers.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import (DEFAULT_CHUNK_SIZE, PAPER_WORKLOADS, enumerate_space,
                        evaluate_space, normalized_report, pareto_mask)
from repro.data.synthetic import eval_image_set, image_batch
from repro.models import cnn
from repro.optim import sgd_nesterov, paper_step_decay

PE_TYPES = ("fp32", "int16", "lightpe1", "lightpe2")
QAT_DEPTH = 8      # ResNet depth of the QAT runs
QAT_BATCH = 64     # images per training step


def qat_setup(pe: str, trial: int = 0, depth: int = QAT_DEPTH):
    """Initial ``(params, opt_state, step)`` of one QAT run under PE type
    ``pe``'s numerics; ``step(params, opt_state, batch)`` is the jitted
    training step and returns ``(params, opt_state, loss)``."""
    params = cnn.resnet_init(jax.random.PRNGKey(trial), depth=depth,
                             n_classes=10)
    opt = sgd_nesterov(paper_step_decay(0.02, 80), weight_decay=5e-4)

    @jax.jit
    def step(params, ostate, batch):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: cnn.cnn_loss(cnn.resnet_apply, p, batch, pe),
            has_aux=True)(params)
        params, ostate = opt.update(grads, ostate, params)
        return params, ostate, loss

    return params, opt.init(params), step


def train_acc(pe: str, depth: int = QAT_DEPTH, steps: int = 200,
              trials: int = 2):
    accs = []
    for trial in range(trials):
        params, ostate, step = qat_setup(pe, trial, depth)
        for i in range(steps):
            params, ostate, _ = step(params, ostate,
                                     image_batch(trial, i, QAT_BATCH, 10))
        ev = eval_image_set(0, 512, 10)
        logits = cnn.resnet_apply(params, ev["images"], pe)
        accs.append(float(jnp.mean(
            (jnp.argmax(logits, -1) == ev["labels"]).astype(jnp.float32))))
    return float(np.mean(accs))


def run(steps: int = 200, max_points: int | None = None, trials: int = 2):
    rows = []
    space = enumerate_space(max_points=max_points, seed=0)
    res = evaluate_space(space, PAPER_WORKLOADS["resnet20-cifar10"](),
                         chunk_size=DEFAULT_CHUNK_SIZE)
    rep = normalized_report(res, space)

    t0 = time.perf_counter()
    accs = {pe: train_acc(pe, steps=steps, trials=trials)
            for pe in PE_TYPES}
    dt = (time.perf_counter() - t0) * 1e6

    # Fig. 5: accuracy vs best perf/area; Fig. 6: accuracy vs best energy
    pts5 = np.array([[rep[pe]["norm_perf_per_area"], accs[pe]]
                     for pe in PE_TYPES])
    on_front5 = np.asarray(pareto_mask(jnp.asarray(pts5)))
    pts6 = np.array([[-rep[pe]["norm_energy"], accs[pe]] for pe in PE_TYPES])
    on_front6 = np.asarray(pareto_mask(jnp.asarray(pts6)))
    for i, pe in enumerate(PE_TYPES):
        rows.append(emit(
            f"fig5_6_{pe}", dt / len(PE_TYPES),
            f"acc={accs[pe]:.3f};norm_ppa={pts5[i, 0]:.2f};"
            f"norm_energy={rep[pe]['norm_energy']:.3f};"
            f"pareto_fig5={bool(on_front5[i])};"
            f"pareto_fig6={bool(on_front6[i])}"))
    lp_on_front = (on_front5[2] or on_front5[3]) and \
        (on_front6[2] or on_front6[3])
    rows.append(emit(
        "fig5_6_claim", 0.0,
        f"lightpes_on_pareto_front={bool(lp_on_front)};"
        f"acc_gap_lpe1_vs_fp32={accs['fp32'] - accs['lightpe1']:.3f};"
        f"paper_claim=on_par_accuracy,LightPEs_on_front"))
    return rows


if __name__ == "__main__":
    run()
