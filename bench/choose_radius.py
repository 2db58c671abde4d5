#!/usr/bin/env python3
"""How the radius of a near-duplicate radius cell was chosen.

    python3 bench/choose_radius.py --config nytimes-bow-d4096 --traffic radius-near --seeds 1 2 3

For each seed: the corpus as the benchmark draws it, near-duplicate queries
as the cell makes them (stored rows with `drop_fraction` of their entries
dropped), and the float64 reference distance of every stored row to each
query.  Prints, per seed, the quantiles over queries of the distance to
the j-th nearest row, and for a few radii how many rows a query finds.
Runs on the chip: it draws and sketches the whole corpus.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH)]

import corpus as corpus_mod  # noqa: E402
import reference as ref  # noqa: E402

N_QUERIES = 64


def distances(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """(queries, rows) float64 reference distances."""
    import jax
    c = corpus_mod.corpus_of(cfg)
    rows, gen = cfg["corpus"]["rows"], cfg["corpus"]["gen_rows"]
    d = cfg["sketch"]["sketch_dim"]
    psi, pi = _seeds(seed)
    rng = np.random.default_rng([seed, 3])
    src = np.sort(rng.choice(rows, N_QUERIES, replace=False))
    stream = corpus_mod.Stream(c, seed, corpus_mod.CORPUS_STREAM)
    q_idx, q_val = [], []
    for b, s in enumerate(range(0, rows, gen)):
        local = src[(src >= s) & (src < s + gen)] - s
        if len(local):
            idx, val = stream.batch(b, gen)
            q_idx.append(np.asarray(idx)[local])
            q_val.append(np.asarray(val)[local])
    q_idx, q_val = np.concatenate(q_idx), np.concatenate(q_val)
    q_idx, q_val = corpus_mod.drop_entries(
        q_idx, q_val, traffic["drop_fraction"], np.random.default_rng(seed))
    qbits = ref.np_unpack(ref.np_sketch(d, psi, pi, q_idx, q_val), d)
    bf = ref.BruteForce(qbits, cfg["sketch"]["metric"], d, keep_all=True)
    for b, s in enumerate(range(0, rows, gen)):
        n = min(gen, rows - s)
        idx, val = stream.batch(b, gen)
        bf.add_batch(ref.ref_bits(idx, val, d=d, psi_seed=psi, pi_seed=pi),
                     np.arange(s, s + n))
    jax.block_until_ready(bf.qbits)
    _, dist, _, _, _, _ = bf._merged()
    return np.sort(dist, axis=1)


def _seeds(seed: int) -> tuple[int, int]:
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.core.cabin import CabinParams
    p = CabinParams.create(1, 128, seed=seed % (2**31))
    return p.psi_seed, p.pi_seed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--radii", type=float, nargs="*", default=[])
    args = ap.parse_args()
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{args.traffic}.json")
                         .read_text())
    for seed in args.seeds:
        dist = distances(cfg, traffic, seed)
        out = {"seed": seed, "queries": N_QUERIES,
               "jth_nearest_quantiles_10_50_90": {
                   j: np.quantile(dist[:, j - 1], [0.1, 0.5, 0.9]).round(3)
                   .tolist() for j in (1, 2, 3, 5, 10, 30, 100)}}
        for r in args.radii:
            hits = (dist < r).sum(axis=1)
            out[f"hits_at_{r:g}_min_median_max"] = [
                int(hits.min()), float(np.median(hits)), int(hits.max())]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
