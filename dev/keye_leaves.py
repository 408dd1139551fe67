"""One run of the Keye cell under the harness's own check, and what the
check saw of the INDEXER's objective:

    chiprun -- python3 dev/keye_leaves.py --seed 3000000001

``benchmarks/run.py`` as the driver calls it (``--trace 0`` or 1), with
``kinds/train.py``'s ``check`` watched, not changed: after it the
gradient norm of each of the indexer's five leaves in each layer (they
are trained by L_I alone), the floor the check judges every leaf against
(``GRAD_FLOOR`` x the largest leaf norm) and each leaf's error as the
check computes it. A leaf under the floor would read small whatever its
gradient is; one over it reads 1 if L_I's gradient is dropped. PERF.md
section 6 (PR 31) quotes it. ``--rehearsal`` walks the same code at the
data files' tiny widths on any backend and is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", default="3000000001")
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--cell", default="keye-vl-2.0-30b-a3b.train.seq16384")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from benchmarks.builders import keye as builder
    from benchmarks.kinds import train
    from benchmarks.reference import keye as ref

    seen = {}
    real = (ref.loss_and_grads, builder.reference_weights, train.check)

    def loss_and_grads(*a):
        out = real[0](*a)
        seen["ref"] = out[1]
        return out

    def reference_weights(tree, cfg):
        seen["sys"] = real[1](tree, cfg)      # the check's last: g_sys
        return seen["sys"]

    def check(*a, **k):
        out = real[2](*a, **k)
        flat = jax.tree_util.tree_flatten_with_path(seen["sys"])[0]
        refs = jax.tree.leaves(seen["ref"])
        norms = [float(jnp.linalg.norm(r)) for r in refs]
        floor = train.GRAD_FLOOR * max(norms)
        print(f"leaves: floor {floor:.4e} ({train.GRAD_FLOOR} x the "
              f"largest leaf norm {max(norms):.4e})", flush=True)
        for (path, g), r, norm in zip(flat, refs, norms):
            name = jax.tree_util.keystr(path)
            if any(f"'{leaf}'" in name for leaf in ref.INDEXER_LEAVES):
                err = float(jnp.linalg.norm(g.astype(jnp.float32) - r)) \
                    / max(norm, floor)
                print(f"leaves: {name}: reference norm {norm:.4e} "
                      f"({norm / floor:.2f} x the floor), error {err:.4f}",
                      flush=True)
        return out

    ref.loss_and_grads, builder.reference_weights, train.check = (
        loss_and_grads, reference_weights, check)
    argv = ["--workload", args.cell, "--seed", args.seed, "--seconds",
            args.seconds, "--trace", args.trace]
    return run.main(argv + (["--rehearsal"] if args.rehearsal else []))


if __name__ == "__main__":
    sys.exit(main())
