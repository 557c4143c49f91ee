"""Perf-regression gate: diff a fresh bench artifact against the latest
BENCH baseline and exit nonzero on regression.

The repo's first *enforceable* perf trajectory (ISSUE 3): every round the
driver captures a `BENCH_r*.json`; this gate compares a freshly produced
`bench_full.json` against the newest of those baselines on thirteen axes —

- **throughput / step time**: the headline resident-tier
  samples/sec/chip (`value`) must not fall below
  `--value-threshold` (default 0.3) of the baseline.  The default is
  wide enough to catch collapses only: the run-to-run spread on the
  chip is not measured yet (root PERF.md), and the threshold should
  tighten to it once the benchmark's cells record it.
- **goodput fraction**: the e2e tiers' mean device-step fraction of
  wall (`goodput.goodput_fraction_mean`, emitted by bench.py from the
  goodput ledger) must not drop more than `--goodput-drop` (absolute,
  default 0.1) below the baseline.
- **compile count**: total observed XLA compiles
  (`xla_compiles.total`) must not exceed `baseline * --compile-factor
  + 2` — a recompile explosion (a shape leak, a lost cache) is a perf
  bug even when the steady-state rate survives it.
- **e2e ceiling fraction**: `e2e_cached_disk_fraction_of_ceiling` (the
  end-to-end rate normalized by the live-probed H2D link ceiling, so
  immune to link drift) must not drop more than `--e2e-ceiling-drop`
  (absolute, default 0.2) below the baseline: the guard that future
  changes cannot silently re-serialize the epoch loop the overlap
  engine (ISSUE 4) pipelined.
- **cold-ingest throughput**: `e2e_cold_disk_samples_per_sec_per_chip`
  must not fall below `--cold-drop` (ratio, default 0.3) of the
  baseline — the guard on the parallel ingest pool + wire-format
  cache-v2 cold path (ISSUE 5).
- **device HBM peak**: `device_hbm_peak_bytes` (the device flight
  recorder's watermark, ISSUE 6) must not exceed `baseline *
  --hbm-factor` (default 1.5) — a memory-footprint explosion is a
  capacity regression (the next batch-size bump OOMs) even when
  throughput survives it.
- **serving throughput**: `serving_scores_per_sec` (the scoring
  daemon's open-loop loadtest capacity at its p99 target, ISSUE 7 —
  bench.py's serving rollup) must not fall below `--serving-drop`
  (ratio, default 0.3) of the baseline: the guard on the
  micro-batching serving plane (a re-serialized dispatch loop, a lost
  batcher, a per-request lock would all collapse it).
- **serving p99 latency**: `serving_p99_ms` (the capacity run's exact
  open-loop p99, ISSUE 8) must not exceed `baseline * --p99-factor`
  (default 3.0) — the latency axis of the serving SLO: throughput can
  survive a change that silently triples tail latency (a lost stage
  overlap, a blocking journal write on the dispatch path), and p99 is
  the serving figure of merit (arxiv 2605.25645).  Wide factor on
  purpose: shared-host p99s swing with co-tenant load.
- **sparse-embed speedup**: `ladder_deepfm_4mvocab_sparse_speedup`
  (the 4M-vocab DeepFM sparse-vs-dense A/B, ISSUE 10) must not fall
  below `min(--sparse-floor, baseline)` — floor-style because the
  field is already a same-run ratio: the engine's contract is "sparse
  must not lose" (1.0), ratcheting in once a baseline reaches it while
  pre-engine 0.7x baselines keep gating against themselves.
- **FT-Transformer MFU**: `ft_transformer_mfu` (the fused
  attention+FFN block's rung on the model ladder, ISSUE 11 — the
  roofline push's figure of merit) must not fall below
  `min(--ft-mfu-floor, baseline)` — the same ratchet-floor style as
  the sparse axis: MFU is normalized by the part's peak (a same-run
  ratio), pre-fusion 0.058 baselines keep gating against themselves,
  and once a fused round lands the floor holds.
- **fleet scaling efficiency**: `fleet_scaling_efficiency` (the
  2-daemon in-proc fleet's scores/s divided by `n_daemons x` the
  single-daemon capacity, ISSUE 12 — bench.py's fleet rollup) must
  not fall below `min(--fleet-eff-floor, baseline)` — ratchet-floor
  style because the field is already a same-run ratio: a serialized router, a lost connection
  pool, or a head-of-line lock would collapse it toward 1/n while
  single-daemon capacity survives.
- **train scaling efficiency**: `train_scaling_efficiency` (the pod
  data plane's ingest-scaling ratio from bench.py's multi-host dryrun
  sweep, ISSUE 20 — single-host ingest seconds divided by `n_hosts x`
  the slowest host's ingest seconds at the widest sweep width) must
  not fall below `min(--train-eff-floor, baseline)` — ratchet-floor
  style like the fleet axis because the field is a same-run ratio: a
  broken shard assignment that piles files
  onto one host, or a per-host fixed cost that swamps the sharded
  ingest, collapses it toward 1/n while the single-host parse axes
  stay green.
- **serving cold-start**: `serving_cold_start_ms` (time-from-spawn to
  the first healthy wire response on the AOT leg of bench.py's
  `local:2` fleet drill, ISSUE 19) must not exceed `baseline *
  --cold-start-factor` (default 3.0) — a lost AOT pack (fingerprint
  drift, broken manifest, a disabled pre-warm) silently degrades the
  leg to live jit compiles and multiplies the spawn-to-ready time,
  while steady-state throughput axes never notice.

The e2e ceiling axis additionally carries a ratchet FLOOR
(`--e2e-ceiling-floor`, default 0.5): once a non-degraded baseline
records a healthy overlap fraction, the limit is
`max(baseline - drop, min(floor, baseline))` — an absolute-drop-only
limit would let the fraction bleed 0.2 per round forever.  Baselines
stamped `degraded_accelerator` (bench.py's preflight) skip the floor:
their fractions were measured on broken hardware.

Checks whose fields are missing on either side are SKIPPED (pre-ledger
baselines carry no goodput/compile fields; pre-flight-recorder ones no
device fields), never failed — older baselines keep gating the axes
they do carry.

`--check-only` is the tier-1 spelling (wired via
tests/test_introspect.py, `perf` marker): a missing or corrupt baseline
/ fresh artifact degrades to a journaled warning (`perf_gate_warning`
when SHIFU_TPU_METRICS_DIR is configured) and exit 0 — the gate must
never hard-fail a checkout that simply has no bench artifacts yet.
Without it, missing inputs exit 2 (usage error, distinct from a real
regression's 1).

Usage:
    python tools/perf_gate.py                       # repo-root defaults
    python tools/perf_gate.py --fresh bench_full.json \
        --baseline BENCH_r05.json [--json] [--check-only]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

EXIT_PASS = 0
EXIT_REGRESSION = 1
EXIT_USAGE = 2


def find_latest_baseline(root: str = _REPO) -> str | None:
    """Newest BENCH_r*.json by round number (the driver's capture).

    Rounds whose artifact is flagged `degraded_accelerator` (captured on
    a host bench.py itself judged unfit) are skipped: gating against a
    collapsed baseline would wave every future regression through.  The
    newest HEALTHY round is the baseline; an unreadable candidate is
    skipped the same way.
    """
    rounds: list[tuple[int, str]] = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    for _n, path in sorted(rounds, reverse=True):
        try:
            if not load_artifact(path).get("degraded_accelerator"):
                return path
        except (OSError, ValueError):
            continue
    return rounds and sorted(rounds, reverse=True)[0][1] or None


def load_artifact(path: str) -> dict:
    """A bench artifact dict, whichever wrapper it arrived in: the
    driver's capture ({"parsed": {...headline...}}), bench_full.json
    (the full dict), or a raw headline dict."""
    with open(path) as f:
        d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(f"{path}: not a JSON object")
    parsed = d.get("parsed")
    if isinstance(parsed, dict) and "value" in parsed:
        return parsed
    if "value" not in d and "goodput" not in d:
        raise ValueError(f"{path}: no bench fields (value/goodput) found")
    return d


def _num(d: dict, *keys):
    cur = d
    for k in keys:
        if not isinstance(cur, dict) or k not in cur:
            return None
        cur = cur[k]
    return cur if isinstance(cur, (int, float)) else None


def run_gate(fresh: dict, baseline: dict, value_threshold: float = 0.3,
             goodput_drop: float = 0.1,
             compile_factor: float = 2.0,
             e2e_ceiling_drop: float = 0.2,
             cold_drop: float = 0.3,
             hbm_factor: float = 1.5,
             serving_drop: float = 0.3,
             p99_factor: float = 3.0,
             sparse_floor: float = 1.0,
             ft_mfu_floor: float = 0.25,
             fleet_eff_floor: float = 0.6,
             train_eff_floor: float = 0.6,
             e2e_ceiling_floor: float = 0.5,
             cold_start_factor: float = 3.0) -> dict:
    """The comparison itself (pure — unit-tested on synthetic pairs).
    Returns {"checks": [...], "verdict": "PASS"|"REGRESSION"}."""
    checks: list[dict] = []

    def check(name, fresh_v, base_v, ok, limit) -> None:
        checks.append({"name": name, "fresh": fresh_v, "baseline": base_v,
                       "limit": limit,
                       "status": ("SKIP" if ok is None
                                  else "OK" if ok else "REGRESSION")})

    fv, bv = _num(fresh, "value"), _num(baseline, "value")
    if fv is None or bv is None or bv <= 0:
        check("throughput_samples_per_sec_per_chip", fv, bv, None, None)
    else:
        limit = bv * value_threshold
        check("throughput_samples_per_sec_per_chip", fv, bv,
              fv >= limit, round(limit, 1))

    fg = _num(fresh, "goodput", "goodput_fraction_mean")
    bg = _num(baseline, "goodput", "goodput_fraction_mean")
    if fg is None or bg is None:
        check("goodput_fraction_mean", fg, bg, None, None)
    else:
        limit = bg - goodput_drop
        check("goodput_fraction_mean", fg, bg, fg >= limit, round(limit, 4))

    fc = _num(fresh, "xla_compiles", "total")
    bc = _num(baseline, "xla_compiles", "total")
    if fc is None or bc is None:
        check("xla_compile_count", fc, bc, None, None)
    else:
        limit = bc * compile_factor + 2
        check("xla_compile_count", fc, bc, fc <= limit, round(limit, 1))

    # e2e ceiling fraction: the link-normalized end-to-end number (rows/s
    # as a fraction of the measured H2D ceiling — immune to link drift,
    # unlike the absolute rate).  A drop here means the epoch loop
    # re-serialized (lost overlap, a reintroduced blocking eval, a dead
    # feeder) even when raw throughput noise hides it.  Absolute
    # tolerance: the bracketing H2D probes still leave some drift in the
    # normalization (docs/PERF.md).
    fe = _num(fresh, "e2e_cached_disk_fraction_of_ceiling")
    be = _num(baseline, "e2e_cached_disk_fraction_of_ceiling")
    if fe is None or be is None:
        check("e2e_ceiling_fraction", fe, be, None, None)
    else:
        limit = be - e2e_ceiling_drop
        if not baseline.get("degraded_accelerator"):
            # ratchet floor (ISSUE 11): drop-only limits compound — 0.2
            # bled per round walks any fraction to zero in N rounds.  A
            # healthy baseline at/above the floor is held to the floor;
            # below it, to itself.  Degraded-host baselines (bench.py's
            # preflight stamp) measured their fraction on broken
            # hardware and don't get to set one.
            limit = max(limit, min(e2e_ceiling_floor, be))
        check("e2e_ceiling_fraction", fe, be, fe >= limit, round(limit, 4))

    # cold-ingest throughput: the end-to-end cold-start rate (first train
    # from disk: inflate+parse+project+quantize+H2D+train).  The parallel
    # ingest pool + v2 cache (ISSUE 5) bought this axis; a drop below the
    # ratio threshold means someone re-serialized the cold path (a lost
    # pool, a reintroduced raw-float32 double-write).  Ratio-style like the
    # headline check: absolute rates move with the host and the link.
    fcold = _num(fresh, "e2e_cold_disk_samples_per_sec_per_chip")
    bcold = _num(baseline, "e2e_cold_disk_samples_per_sec_per_chip")
    if fcold is None or bcold is None or bcold <= 0:
        check("e2e_cold_throughput", fcold, bcold, None, None)
    else:
        limit = bcold * cold_drop
        check("e2e_cold_throughput", fcold, bcold, fcold >= limit,
              round(limit, 1))

    # device HBM peak: the watermark the flight recorder records at epoch
    # boundaries (ISSUE 6).  Factor-style upper bound: allocator behavior
    # wobbles run to run, but a 1.5x footprint jump means a real new
    # resident (a lost donation, a duplicated table) and eats the headroom
    # the next scale-up needs.  SKIP when either side predates the field.
    fh = _num(fresh, "device_hbm_peak_bytes")
    bh = _num(baseline, "device_hbm_peak_bytes")
    if fh is None or bh is None or bh <= 0:
        check("device_hbm_peak_bytes", fh, bh, None, None)
    else:
        limit = bh * hbm_factor
        check("device_hbm_peak_bytes", fh, bh, fh <= limit, round(limit, 1))

    # serving throughput: the daemon's loadtest capacity (scores/s at the
    # p99 target, open-loop — ISSUE 7).  Ratio-style like the headline
    # and cold axes: the shared host's absolute numbers swing with
    # co-tenant load.  SKIP when either side predates the serving plane.
    fsv = _num(fresh, "serving_scores_per_sec")
    bsv = _num(baseline, "serving_scores_per_sec")
    if fsv is None or bsv is None or bsv <= 0:
        check("serving_scores_per_sec", fsv, bsv, None, None)
    else:
        limit = bsv * serving_drop
        check("serving_scores_per_sec", fsv, bsv, fsv >= limit,
              round(limit, 1))

    # serving p99: the latency leg of the serving SLO (ISSUE 8).  Upper
    # bound, factor-style: a p99 tripling is a tail-latency regression
    # even when capacity holds (the stage histograms in the serving
    # telemetry say WHICH stage ate it).  SKIP when either side predates
    # the field or recorded a null p99 (capacity below the start rate).
    fp = _num(fresh, "serving_p99_ms")
    bp = _num(baseline, "serving_p99_ms")
    if fp is None or bp is None or bp <= 0:
        check("serving_p99_ms", fp, bp, None, None)
    else:
        limit = bp * p99_factor
        check("serving_p99_ms", fp, bp, fp <= limit, round(limit, 2))

    # sparse-embed speedup: the 4M-vocab DeepFM sparse-vs-dense A/B ratio
    # (ISSUE 10's engine).  Floor-style, not ratio-of-baseline: the number
    # IS already a same-run ratio, and the engine's contract
    # is "sparse must not lose" (>= 1.0).  The floor ratchets in via
    # min(floor, baseline): a pre-engine baseline that recorded the
    # scatter path's 0.7x keeps passing against itself, while any round
    # whose baseline reached the floor is held to it.  SKIP when either
    # side predates the A/B.
    fsp = _num(fresh, "ladder_deepfm_4mvocab_sparse_speedup")
    bsp = _num(baseline, "ladder_deepfm_4mvocab_sparse_speedup")
    if fsp is None or bsp is None or bsp <= 0:
        check("sparse_embed_speedup", fsp, bsp, None, None)
    else:
        limit = min(sparse_floor, bsp)
        check("sparse_embed_speedup", fsp, bsp, fsp >= limit,
              round(limit, 2))

    # FT-Transformer MFU: the fused-block rung's model-flop utilization
    # (ISSUE 11's roofline push).  Ratchet-floor like the sparse axis:
    # MFU is peak-normalized (drift-immune), so min(floor, baseline)
    # lets the unfused 0.058 era gate against itself while any round
    # whose baseline reached the floor is held there — a silently
    # disengaged fusion (lost gate, dead kill-switch default) collapses
    # the number back to unfused and fails here.  SKIP when either side
    # predates the field.
    fft = _num(fresh, "ft_transformer_mfu")
    bft = _num(baseline, "ft_transformer_mfu")
    if fft is None or bft is None or bft <= 0:
        check("ft_transformer_mfu", fft, bft, None, None)
    else:
        limit = min(ft_mfu_floor, bft)
        check("ft_transformer_mfu", fft, bft, fft >= limit,
              round(limit, 4))

    # fleet scaling efficiency: the 2-daemon in-proc fleet's scores/s
    # over n_daemons x the single-daemon capacity (ISSUE 12's router +
    # fleet plane).  Ratchet-floor like the sparse and MFU axes: the
    # field is a same-run ratio, so it's immune to host drift, and a
    # regression here means the ROUTING layer serialized (a lost
    # per-member connection pool, a global lock on the ring walk, a
    # hedge storm) while raw single-daemon capacity looks fine.  SKIP
    # when either side predates the fleet plane.
    ffe = _num(fresh, "fleet_scaling_efficiency")
    bfe = _num(baseline, "fleet_scaling_efficiency")
    if ffe is None or bfe is None or bfe <= 0:
        check("fleet_scaling_efficiency", ffe, bfe, None, None)
    else:
        limit = min(fleet_eff_floor, bfe)
        check("fleet_scaling_efficiency", ffe, bfe, ffe >= limit,
              round(limit, 4))

    # train scaling efficiency: the pod data plane's ingest-scaling
    # ratio from the multi-host dryrun sweep (ISSUE 20).  Same
    # ratchet-floor shape as the fleet axis — the field is a same-run
    # ratio of ingest seconds, immune to co-tenant drift, and a
    # regression means the SHARD ASSIGNMENT went lopsided (one host
    # ingesting most of the bytes) or a per-host fixed cost grew to
    # rival the sharded ingest itself, while the single-host parse
    # axes stay green.  SKIP when either side predates the pod data
    # plane.
    fte = _num(fresh, "train_scaling_efficiency")
    bte = _num(baseline, "train_scaling_efficiency")
    if fte is None or bte is None or bte <= 0:
        check("train_scaling_efficiency", fte, bte, None, None)
    else:
        limit = min(train_eff_floor, bte)
        check("train_scaling_efficiency", fte, bte, fte >= limit,
              round(limit, 4))

    # serving cold-start: spawn-to-first-healthy-response on the AOT
    # leg of bench.py's fleet drill (ISSUE 19).  Upper bound,
    # factor-style like p99: the number is wall-clock on a shared host,
    # so the wide factor catches the real failure — a silently lost AOT
    # pack (fingerprint drift, a broken manifest) drops the leg back to
    # live jit compiles and multiplies the time, while run-to-run
    # deserialize noise stays inside the band.  SKIP when either side
    # predates the drill.
    fcs = _num(fresh, "serving_cold_start_ms")
    bcs = _num(baseline, "serving_cold_start_ms")
    if fcs is None or bcs is None or bcs <= 0:
        check("serving_cold_start_ms", fcs, bcs, None, None)
    else:
        limit = bcs * cold_start_factor
        check("serving_cold_start_ms", fcs, bcs, fcs <= limit,
              round(limit, 2))

    regressed = [c for c in checks if c["status"] == "REGRESSION"]
    return {"checks": checks,
            "verdict": "REGRESSION" if regressed else "PASS"}


def _journal(kind: str, **fields) -> None:
    """Best-effort journal hook: lands in SHIFU_TPU_METRICS_DIR when
    configured, silently no-ops otherwise (the gate must work in a bare
    checkout with no telemetry and no jax)."""
    try:
        from shifu_tpu import obs
        if obs.configure_from_env():
            obs.event(kind, **fields)
            obs.flush()
    except Exception:
        pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="perf_gate",
        description="compare a fresh bench artifact against the latest "
                    "BENCH_r*.json baseline; exit 1 on regression")
    p.add_argument("--fresh", default=os.path.join(_REPO, "bench_full.json"),
                   help="fresh bench artifact (default: repo bench_full.json)")
    p.add_argument("--baseline", default=None,
                   help="baseline artifact (default: newest BENCH_r*.json)")
    p.add_argument("--value-threshold", type=float, default=0.3,
                   help="fresh throughput must be >= baseline * this "
                        "fraction (default 0.3 — catches collapses; "
                        "the chip's run-to-run spread is not measured "
                        "yet)")
    p.add_argument("--goodput-drop", type=float, default=0.1,
                   help="max absolute drop in mean goodput fraction")
    p.add_argument("--compile-factor", type=float, default=2.0,
                   help="fresh compile count must be <= baseline * this + 2")
    p.add_argument("--e2e-ceiling-drop", type=float, default=0.2,
                   help="max absolute drop in e2e_cached_disk_fraction_of_"
                        "ceiling (the link-normalized e2e number — a drop "
                        "means the epoch loop re-serialized)")
    p.add_argument("--cold-drop", type=float, default=0.3,
                   help="fresh e2e_cold_disk_samples_per_sec_per_chip must "
                        "be >= baseline * this fraction (the cold-ingest "
                        "axis: parallel parse pool + v2 cache, ISSUE 5)")
    p.add_argument("--hbm-factor", type=float, default=1.5,
                   help="fresh device_hbm_peak_bytes must be <= baseline * "
                        "this factor (the flight recorder's watermark, "
                        "ISSUE 6; SKIP when either side lacks the field)")
    p.add_argument("--serving-drop", type=float, default=0.3,
                   help="fresh serving_scores_per_sec must be >= baseline "
                        "* this fraction (the scoring daemon's loadtest "
                        "capacity, ISSUE 7; SKIP when either side lacks "
                        "the field)")
    p.add_argument("--p99-factor", type=float, default=3.0,
                   help="fresh serving_p99_ms must be <= baseline * this "
                        "factor (the serving SLO's latency axis, ISSUE 8; "
                        "SKIP when either side lacks the field)")
    p.add_argument("--sparse-floor", type=float, default=1.0,
                   help="fresh ladder_deepfm_4mvocab_sparse_speedup must "
                        "be >= min(this, baseline) (the sparse embedding "
                        "engine's A/B, ISSUE 10; SKIP when either side "
                        "lacks the field)")
    p.add_argument("--ft-mfu-floor", type=float, default=0.25,
                   help="fresh ft_transformer_mfu must be >= min(this, "
                        "baseline) (the fused attention+FFN block's rung, "
                        "ISSUE 11; SKIP when either side lacks the field)")
    p.add_argument("--fleet-eff-floor", type=float, default=0.6,
                   help="fresh fleet_scaling_efficiency must be >= "
                        "min(this, baseline) (the fleet's scores/s over "
                        "n_daemons x single-daemon capacity, ISSUE 12; "
                        "SKIP when either side lacks the field)")
    p.add_argument("--train-eff-floor", type=float, default=0.6,
                   help="fresh train_scaling_efficiency must be >= "
                        "min(this, baseline) (the pod data plane's "
                        "ingest scaling from the multi-host dryrun "
                        "sweep, ISSUE 20; SKIP when either side lacks "
                        "the field)")
    p.add_argument("--cold-start-factor", type=float, default=3.0,
                   help="fresh serving_cold_start_ms must be <= baseline * "
                        "this factor (the AOT-packed fleet cold-start "
                        "drill, ISSUE 19; SKIP when either side lacks the "
                        "field)")
    p.add_argument("--e2e-ceiling-floor", type=float, default=0.5,
                   help="ratchet floor on e2e_cached_disk_fraction_of_"
                        "ceiling: a non-degraded baseline at/above this "
                        "holds the limit at the floor instead of "
                        "baseline - drop (drop-only limits compound)")
    p.add_argument("--check-only", action="store_true",
                   help="tier-1 mode: missing/corrupt artifacts degrade to "
                        "a journaled warning and exit 0")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report instead of text")
    args = p.parse_args(argv)

    baseline_path = args.baseline or find_latest_baseline()
    problems = []
    fresh = baseline = None
    if baseline_path is None:
        problems.append("no BENCH_r*.json baseline found")
    else:
        try:
            baseline = load_artifact(baseline_path)
        except (OSError, ValueError) as e:
            problems.append(f"baseline unreadable: {e}")
    try:
        fresh = load_artifact(args.fresh)
    except (OSError, ValueError) as e:
        problems.append(f"fresh artifact unreadable: {e}")

    if problems:
        msg = "; ".join(problems)
        if args.check_only:
            # degraded, not failed: a checkout with no bench artifacts
            # (or a half-written one) must never fail tier-1
            _journal("perf_gate_warning", problems=problems)
            report = {"verdict": "SKIPPED", "problems": problems}
            print(json.dumps(report) if args.json
                  else f"perf-gate: SKIPPED — {msg}")
            return EXIT_PASS
        print(f"perf-gate: {msg}", file=sys.stderr, flush=True)
        return EXIT_USAGE

    report = run_gate(fresh, baseline,
                      value_threshold=args.value_threshold,
                      goodput_drop=args.goodput_drop,
                      compile_factor=args.compile_factor,
                      e2e_ceiling_drop=args.e2e_ceiling_drop,
                      cold_drop=args.cold_drop,
                      hbm_factor=args.hbm_factor,
                      serving_drop=args.serving_drop,
                      p99_factor=args.p99_factor,
                      sparse_floor=args.sparse_floor,
                      ft_mfu_floor=args.ft_mfu_floor,
                      fleet_eff_floor=args.fleet_eff_floor,
                      train_eff_floor=args.train_eff_floor,
                      e2e_ceiling_floor=args.e2e_ceiling_floor,
                      cold_start_factor=args.cold_start_factor)
    report["fresh"] = args.fresh
    report["baseline"] = baseline_path
    _journal("perf_gate", verdict=report["verdict"],
             baseline=os.path.basename(baseline_path),
             checks={c["name"]: c["status"] for c in report["checks"]})
    if args.json:
        print(json.dumps(report))
    else:
        print(f"perf-gate: {report['verdict']} "
              f"(fresh {args.fresh} vs baseline "
              f"{os.path.basename(baseline_path)})")
        for c in report["checks"]:
            print(f"  {c['status']:>10}  {c['name']}: "
                  f"fresh={c['fresh']} baseline={c['baseline']} "
                  f"limit={c['limit']}")
    return (EXIT_PASS if report["verdict"] == "PASS" else EXIT_REGRESSION)


if __name__ == "__main__":
    sys.exit(main())
