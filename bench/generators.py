"""Input generators of the benchmark, kept here so the program cannot move them.

Copies of the program's own generators, so that a later change to the
program never changes what the benchmark feeds it:

* ``APP_SPECS`` and ``build_app``: the eight Table-1 applications at their
  published synapse, neuron and spike totals (``repro.core.apps``);
* ``feedforward`` and ``calibrate_spikes``: the layered sparse connectivity
  and the log-normal spike profile (``repro.core.snn``).

Every function returns the fields of an SNN as a plain dict of numpy arrays
(``n_neurons``, ``pre``, ``post``, ``weight``, ``spikes``, ``layer_of``,
``name``); the harness turns them into the program's ``SNN`` dataclass and
the reference reads them as they are.  The test
``bench/tests/test_bench_generators.py`` holds each copy bit-identical to
the program's generator.
"""

from __future__ import annotations

import numpy as np

#: (name, synapses, neurons, spikes over the recorded run, layer shape,
#: recurrent, seed); 100 recorded iterations per app (Table 1, §6.2)
APP_SPECS = {
    "ImgSmooth": (136_314, 980, 17_600, (4096, 1024), False, 101),
    "EdgeDet": (272_628, 1_372, 22_780, (4096, 1024, 1024, 1024), False, 102),
    "MLP-MNIST": (79_400, 984, 2_395_300, (784, 100, 10), False, 103),
    "HeartEstm": (636_578, 6_952, 3_002_223, (1000, 5000, 952), True, 104),
    "HeartClass": (
        2_396_521, 24_732, 1_036_485, (6724, 13456, 4290, 256, 6), False, 105
    ),
    "CNN-MNIST": (159_553, 5_576, 97_585, (576, 4840, 150, 10), False, 106),
    "LeNet-MNIST": (
        1_029_286, 4_634, 165_997, (1024, 2688, 708, 120, 84, 10), False, 107
    ),
    "LeNet-CIFAR": (
        2_136_560, 18_472, 589_953, (3072, 12288, 3018, 84, 10), False, 108
    ),
}
RECORDED_ITERS = 100


def feedforward(layer_sizes, n_synapses, *, seed, name="snn", recurrent=False):
    """Layered sparse SNN with an exact synapse total (conv-style windows)."""
    rng = np.random.default_rng(seed)
    layer_sizes = list(layer_sizes)
    n_neurons = int(sum(layer_sizes))
    offsets = np.cumsum([0] + layer_sizes)
    layer_of = np.concatenate(
        [np.full(s, i, dtype=np.int32) for i, s in enumerate(layer_sizes)]
    )
    pairs = [(i, i + 1) for i in range(len(layer_sizes) - 1)]
    if recurrent:
        pairs += [(len(layer_sizes) - 1, 1)]
    caps = np.array(
        [layer_sizes[a] * layer_sizes[b] for a, b in pairs], dtype=np.float64
    )
    counts = np.floor(n_synapses * caps / caps.sum()).astype(np.int64)
    counts[-1] += n_synapses - counts.sum()
    pres, posts = [], []
    for (a, b), cnt in zip(pairs, counts):
        sa, sb = layer_sizes[a], layer_sizes[b]
        cnt = int(min(cnt, sa * sb))
        base = cnt // sb
        fan = np.full(sb, base, dtype=np.int64)
        fan[: cnt - int(fan.sum())] += 1
        w = int(min(sa, max(8, np.ceil(1.25 * max(base, 1)))))
        step = max(1, w // 2)
        centers = (np.arange(sb) * (sa / sb)).astype(np.int64)
        starts_w = np.clip((centers // step) * step, 0, max(sa - w, 0))
        src_list, dst_list = [], []
        for j in range(sb):
            f = int(fan[j])
            if f == 0:
                continue
            f = min(f, w)
            src_list.append(rng.choice(w, size=f, replace=False) + starts_w[j])
            dst_list.append(np.full(f, j, dtype=np.int64))
        pres.append(offsets[a] + np.concatenate(src_list))
        posts.append(offsets[b] + np.concatenate(dst_list))
    pre = np.concatenate(pres).astype(np.int32)
    weight = rng.normal(0.0, 0.5, size=pre.size).astype(np.float32)
    return {
        "n_neurons": n_neurons,
        "pre": pre,
        "post": np.concatenate(posts).astype(np.int32),
        "weight": weight,
        "spikes": np.zeros(n_neurons),
        "layer_of": layer_of,
        "name": name,
    }


def calibrate_spikes(snn: dict, total_spikes: float, *, seed: int) -> dict:
    """Log-normal per-neuron spike counts scaled to ``total_spikes``."""
    rng = np.random.default_rng(seed)
    profile = rng.lognormal(mean=0.0, sigma=1.0, size=snn["n_neurons"])
    return {**snn, "spikes": profile * (total_spikes / profile.sum())}


def build_app(name: str) -> dict:
    """One Table-1 application at its published totals."""
    synapses, _, spikes, shape, recurrent, seed = APP_SPECS[name]
    snn = feedforward(
        shape, synapses, seed=seed, name=name, recurrent=recurrent
    )
    snn = calibrate_spikes(
        snn, float(spikes) / RECORDED_ITERS, seed=seed + 7
    )
    if snn["pre"].size != synapses:
        raise ValueError(f"{name}: {snn['pre'].size} synapses, not {synapses}")
    return snn
