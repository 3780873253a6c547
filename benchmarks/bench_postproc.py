"""Paper Table 4: host postprocessing time, outfeed vs top-k strategies.

The paper observed postproc is a small fraction of total runtime, grows
~linearly with accepted samples, and is larger for the chunked-outfeed
strategy (more data crosses to host). Same checks here (claim C6)."""

from __future__ import annotations


from benchmarks.common import render_table, save_result
from repro.core.abc import ABCConfig, run_abc
from repro.epi.data import get_dataset

DAYS = 20
BATCH = 8192


def run(quick: bool = True):
    ds = get_dataset("synthetic_small", num_days=DAYS)
    rows, raw = [], {}
    cases = [
        ("outfeed", 1.6e4, 50), ("outfeed", 1.6e4, 200), ("outfeed", 2.1e4, 50),
        ("topk", 1.6e4, 50), ("topk", 1.6e4, 200),
    ]
    for strategy, tol, target in cases:
        cfg = ABCConfig(
            batch_size=BATCH, tolerance=tol, target_accepted=target,
            chunk_size=1024, strategy=strategy, top_k=64, num_days=DAYS,
            backend="xla_fused", max_runs=4000,
        )
        post = run_abc(ds, cfg, key=0)
        pp = post.phase_s.get("harvest", 0.0)
        frac = pp / max(post.wall_time_s, 1e-9)
        rows.append([strategy, f"{tol:.2g}", target, len(post),
                     f"{pp*1e3:.1f}", f"{frac:.1%}"])
        raw[f"{strategy}_{tol:g}_{target}"] = {
            "postproc_ms": pp * 1e3, "fraction": frac, "accepted": len(post),
        }
    print("\n== Table 4 analogue: host postprocessing ==")
    print(render_table(
        ["strategy", "tol", "target", "accepted", "postproc_ms", "% of total"], rows))
    of = [raw[k]["fraction"] for k in raw if k.startswith("outfeed")]
    print(f"C6: postproc stays minor (max {max(of):.1%} of wall time for outfeed)")
    save_result("table4_postproc", raw)
    return raw


if __name__ == "__main__":
    run()
