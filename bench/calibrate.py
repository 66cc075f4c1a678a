"""Readings that the limits of a cell's check are set from.

    python3 -m bench.calibrate --workload sel-cifar10 --seeds 1 2 3 \\
        --control-seeds 4 5 6

In one process on the chip: the program's reading on each of ``--seeds``
(the lower readings), and the control's on each of ``--control-seeds`` (the
upper ones): the reference computed one precision below the configuration's
(bfloat16 for float32 selection), put in the program's place.  Also the
faults of answers mapped to the wrong rows, planted in the program's
artifact of each control seed: a class's importances permuted or shifted by
one row, its probabilities reversed.  One JSON line per reading, with the
seconds the reference took.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def planted(art, reference, fault: str):
    """The artifact with one fault planted in every class."""
    import numpy as np

    from bench.references import milo_selection as ref

    imp, probs = np.array(art.wre_importance), np.array(art.wre_probs)
    for c in reference:
        r = c.rows
        if fault == "importance_permuted":
            imp[r] = imp[r][np.random.default_rng(0).permutation(len(r))]
        elif fault == "importance_shifted":
            imp[r] = np.roll(imp[r], 1)
        else:
            probs[r] = probs[r][::-1]
    return ref.Artifact(sge_subsets=art.sge_subsets, wre_probs=probs,
                        wre_importance=imp)


def selection_readings(config: dict, traffic: dict, seeds, control_seeds):
    import time

    from bench import data
    from bench.drivers import selection
    from bench.references import milo_selection as ref

    base = None
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        x, y = selection.inputs(config, seed)
        t0 = time.perf_counter()
        reference = ref.reference(x, y, traffic)
        ref_s = time.perf_counter() - t0
        if base is None:
            base = selection.session_config(traffic, data.subseed(seed, 0))
            selection.warm(base, x, y, traffic)
        art = selection.build(base, x, y, data.subseed(seed, 1))
        if seed in seeds:
            yield {"kind": "program", "seed": seed, "reference_s": ref_s,
                   **ref.compare(art, reference, traffic)}
        if seed in control_seeds:
            ctl = ref.control_artifact(x, y, traffic)
            yield {"kind": "control", "seed": seed,
                   **ref.compare(ctl, reference, traffic)}
            for fault in ("importance_permuted", "importance_shifted",
                          "probs_reversed"):
                yield {"kind": fault, "seed": seed, **ref.compare(
                    planted(art, reference, fault), reference, traffic)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from bench.device import NoChipError, require_tpu
    from bench.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.workload(args.workload)
    try:
        require_tpu(int(cell["chips"]))
    except NoChipError as e:
        print(f"bench.calibrate: {e}", file=sys.stderr)
        return 3
    from bench.run import enable_compile_cache

    enable_compile_cache()
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    for r in selection_readings(config, traffic, args.seeds, args.control_seeds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
