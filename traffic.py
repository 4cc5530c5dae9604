#!/usr/bin/env python
"""Traffic lab CLI: open-loop load sweeps over the serving fleet.

Drives mingpt_distributed_tpu/trafficlab end to end: a seeded arrival
process (Poisson / bursty / ramp) is offered at each rung of a load
ladder, every admission policy (fifo / edf / fair) replays the
IDENTICAL arrival trace per rung against a fresh fleet on VirtualClock,
each (rung, policy) cell is graded by the telemetry SLO engine, and the
result is a versioned ``mingpt-traffic/1`` JSON report with the knee
rung (first rung where the named objective fails). Zero wall-clock
reads: a multi-rung sweep finishes in seconds of real time regardless
of the virtual load, and the same seed reproduces the report
byte-for-byte.

Modes:

  sweep (default)     restore the training snapshot (as serve.py does)
                      and sweep it:
                        python traffic.py --arrival poisson:rate=60 \
                            --ladder 1,2,4 --policies fifo,edf --out r.json
                      (--random-init skips the checkpoint: random weights,
                      config dims — latency shape only, no real text)
  self-test           random-init tiny model, 2-rung FIFO-vs-EDF sweep on
                      a deadline-mixed workload; asserts the report
                      strict-parses, the knee is located (objective passes
                      at rung 0, fails at rung 1), EDF >= FIFO on
                      deadline-hit-rate at the overload rung, and a second
                      run is byte-identical — the CI gate
                      (run_tests.sh --selftest-traffic):
                        python traffic.py --selftest-traffic

Knobs: --arrival SPEC (poisson:rate=R | bursty:rate_on=..:rate_off=..:
period=..:duty=.. | ramp:rate0=..:rate1=..:duration=..), --ladder
"f1,f2,..." (load multipliers, strictly increasing), --policies
"fifo,edf,fair", --requests N per rung, --seed, --replicas/--slots
(fleet geometry), --slo SPEC (telemetry/slo.py grammar),
--knee-objective NAME (default: first objective), --chaos-spec SPEC
(ServingFaultInjector grammar — the same sweep graded under crashes),
--controllers "static,auto:..." (SLO-autoscaler axis: every policy
runs once per controller on the identical trace), --shed-watermark D,
--prefix-cache-mb M, --out PATH (report JSON).
"""

from __future__ import annotations

import argparse
import dataclasses
import sys


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="gpt2_config.yaml")
    p.add_argument("--arrival", default="poisson:rate=60.0",
                   help="base arrival spec (see module docstring); the "
                        "ladder multiplies its rates")
    p.add_argument("--ladder", default="1,2,4",
                   help="comma-separated load factors, strictly increasing")
    p.add_argument("--policies", default="fifo,edf",
                   help="admission policies to compare on the identical "
                        "trace (fifo | edf | fair)")
    p.add_argument("--requests", type=int, default=64,
                   help="arrivals per rung")
    p.add_argument("--seed", type=int, default=0,
                   help="replay seed: (seed, specs) fully determine the "
                        "report bytes")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--slots", type=int, default=4,
                   help="KV slots per replica")
    p.add_argument("--tick-s", type=float, default=0.001,
                   help="virtual seconds per fleet scheduling round")
    p.add_argument("--slo", default="default",
                   help="SLO spec to grade each cell with "
                        "(telemetry/slo.py grammar; 'default' = stock "
                        "objectives)")
    p.add_argument("--knee-objective", default=None,
                   help="objective name the knee is located on (default: "
                        "first objective in --slo)")
    p.add_argument("--chaos-spec", default=None,
                   help="ServingFaultInjector spec: grade the same sweep "
                        "under injected faults")
    p.add_argument("--hosts", type=int, default=1,
                   help="> 1 runs every cell on the loopback cross-host "
                        "mesh (--replicas becomes per-host)")
    p.add_argument("--net-chaos-spec", default=None,
                   help="NetworkFaultInjector spec (partition / "
                        "drop_frame / slow_link / host_kill) over the "
                        "host mesh; needs --hosts >= 2")
    p.add_argument("--controllers", default="static",
                   help="comma-separated controller axis: each entry is "
                        "'static' or an 'auto[:k=v...]' SLO-autoscaler "
                        "spec; every policy runs once per controller on "
                        "the identical trace (autoscaled cells are "
                        "labelled policy+auto)")
    p.add_argument("--shed-watermark", type=int, default=None,
                   help="fleet-wide queue depth that sheds new arrivals")
    p.add_argument("--prefix-cache-mb", type=float, default=0.0,
                   help="per-replica shared-prefix KV budget (MiB); >0 "
                        "lets shared-prefix tenants hit the store")
    p.add_argument("--out", default=None,
                   help="write the mingpt-traffic/1 report JSON here")
    p.add_argument("--random-init", action="store_true",
                   help="skip checkpoint restore: random weights at the "
                        "config's dims (scheduling/latency study only)")
    p.add_argument("--selftest-traffic", action="store_true",
                   help="tiny random-init model, canned 2-rung FIFO/EDF "
                        "sweep; asserts knee + policy separation + "
                        "byte-identical replay, then exits")
    p.add_argument("--selftest-controller", action="store_true",
                   help="tiny random-init model, one down-ramp rung, "
                        "static vs SLO-autoscaled cells on the identical "
                        "trace; asserts the controller scales up AND back "
                        "down, beats static on deadline hit-rate and "
                        "cost, and replays byte-identically (report and "
                        "mingpt-control/1 log), then exits")
    p.add_argument("overrides", nargs="*")
    return p


def _parse_ladder(text: str):
    try:
        ladder = tuple(float(f) for f in text.split(",") if f.strip())
    except ValueError:
        raise SystemExit(f"--ladder must be comma-separated floats, "
                         f"got {text!r}")
    if not ladder:
        raise SystemExit("--ladder is empty")
    return ladder


def _sweep_spec(args):
    from mingpt_distributed_tpu.trafficlab import SweepSpec

    spec = SweepSpec(
        arrival=args.arrival,
        ladder=_parse_ladder(args.ladder),
        policies=tuple(p.strip() for p in args.policies.split(",")
                       if p.strip()),
        n_requests=args.requests,
        seed=args.seed,
        n_replicas=args.replicas,
        n_slots=args.slots,
        tick_s=args.tick_s,
        slo=args.slo,
        knee_objective=args.knee_objective,
        chaos_spec=args.chaos_spec,
        n_hosts=args.hosts,
        net_chaos_spec=args.net_chaos_spec,
        controllers=tuple(c.strip() for c in args.controllers.split(",")
                          if c.strip()),
        shed_watermark=args.shed_watermark,
        prefix_cache_mb=args.prefix_cache_mb,
    )
    try:
        spec.validate()
    except ValueError as e:
        raise SystemExit(f"bad sweep parameters: {e}")
    return spec


def _tiny_model():
    """The repo-standard tiny random-init model (serve.py --selftest
    geometry): CPU-fast, real compiled prefill/decode."""
    import jax

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import gpt

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def selftest_mix():
    """The tuned selftest workload: a deadline-tight chat tenant that
    EDF saves under overload, a deadline-free batch tenant whose long
    decodes clog FIFO queues, and a shared-prefix tenant for the
    PrefixKVStore. Geometry chosen so the overload rung's queue waits
    overrun the chat deadline under FIFO but not under EDF."""
    from mingpt_distributed_tpu.trafficlab import TenantSpec, WorkloadMix

    return WorkloadMix(vocab_size=96, tenants=(
        TenantSpec(name="chat", family="chat", weight=3.0,
                   prompt_len=(3, 8), max_new=(2, 4), deadline_s=0.035),
        TenantSpec(name="batch", family="completion", weight=3.0,
                   prompt_len=(4, 10), max_new=(10, 16)),
        TenantSpec(name="assist", family="prefix", weight=2.0,
                   prompt_len=(8, 14), max_new=(2, 6), deadline_s=0.08,
                   prefix_pool=2, prefix_len=6),
    ))


def selftest_sweep_spec(ladder=(1.0, 24.0)):
    """Canned selftest sweep: rung 0 well under the 1x2-slot fleet's
    capacity, the last rung strongly over it (tuned empirically: at 24x
    the p95 queue wait is ~3x the knee threshold)."""
    from mingpt_distributed_tpu.trafficlab import SweepSpec

    return SweepSpec(
        arrival="poisson:rate=40.0",
        ladder=ladder,
        policies=("fifo", "edf"),
        n_requests=40,
        seed=0,
        n_replicas=1,
        n_slots=2,
        slo="ttft_p95<=0.025,shed_rate<=0.5",
        prefix_cache_mb=0.5,
    )


def selftest_traffic(args) -> int:
    """The CI gate (run_tests.sh --selftest-traffic). Asserts, on the
    canned geometry: strict report validation after a JSON round-trip,
    knee located with the pass->fail shape, EDF >= FIFO on
    deadline-hit-rate at the overload rung (same trace — the report's
    trace_sha256 proves it), and byte-identical replay."""
    import json

    from mingpt_distributed_tpu.trafficlab import (
        render_traffic_report,
        run_sweep,
        validate_traffic_report,
    )
    from mingpt_distributed_tpu.trafficlab.report import dump_report

    cfg, params = _tiny_model()
    spec = selftest_sweep_spec()
    mix = selftest_mix()
    report = run_sweep(params, cfg, spec, mix=mix)
    print(render_traffic_report(report))

    rc = 0

    def check(ok: bool, what: str) -> None:
        nonlocal rc
        print(f"selftest-traffic {'OK' if ok else 'FAIL'}: {what}")
        if not ok:
            rc = 1

    # strict validation must survive a serialize/parse round-trip (the
    # report a consumer reads, not the in-memory dict)
    parsed = json.loads(dump_report(report))
    problems = validate_traffic_report(parsed, strict=False)
    check(not problems, f"report strict-parses (problems={problems})")

    knee = parsed.get("knee")
    check(knee is not None and knee["valid"],
          f"knee located with pass->fail shape (knee={knee})")

    last = parsed["rungs"][-1]
    fifo_cell = last["policies"]["fifo"]
    edf_cell = last["policies"]["edf"]
    fifo_hit = fifo_cell["deadline_hit_rate"]
    edf_hit = edf_cell["deadline_hit_rate"]
    check(fifo_hit is not None and edf_hit is not None
          and edf_hit >= fifo_hit,
          f"EDF >= FIFO on deadline-hit-rate at overload rung "
          f"(edf={edf_hit} fifo={fifo_hit})")
    check(edf_hit is not None and fifo_hit is not None
          and edf_hit > fifo_hit,
          "separation is strict on the canned geometry")

    report2 = run_sweep(params, cfg, spec, mix=mix)
    check(dump_report(report) == dump_report(report2),
          "same-seed rerun is byte-identical")

    print("selftest-traffic " + ("PASSED" if rc == 0 else "FAILED"))
    return rc


AUTO_SPEC = ("auto:metric=queue_depth:target=2.0:comfort=0.5"
             ":interval_s=0.002:cooldown_s=0.02:up_after=2:down_after=5"
             ":min_replicas=1:max_replicas=3")


def selftest_controller_spec():
    """Canned controller geometry: a DOWN-ramp so one cell exercises
    both directions — the early burst (~300/s against a 1x2-slot
    fleet) forces scale-ups, the sparse tail (~6/s) leaves the extra
    replicas comfortable long enough to drain back down."""
    from mingpt_distributed_tpu.trafficlab import SweepSpec

    return SweepSpec(
        arrival="ramp:rate0=1400.0:rate1=4.0:duration=0.04",
        ladder=(1.0,),
        policies=("fifo",),
        controllers=("static", AUTO_SPEC),
        n_requests=36,
        seed=0,
        n_replicas=1,
        n_slots=2,
        slo="ttft_p95<=0.025,shed_rate<=0.5",
        prefix_cache_mb=0.5,
    )


def selftest_controller(args) -> int:
    """The CI gate (run_tests.sh --selftest-controller). Static and
    autoscaled cells replay the IDENTICAL down-ramp trace; asserts the
    controller logs >= 1 replica scale-up and >= 1 scale-down, beats
    the static fleet on deadline hit-rate AND cost-model cost at the
    overload rung, the report strict-parses, every control-log line is
    a valid mingpt-control/1 row, and a rerun reproduces both the
    report and the control log byte-for-byte."""
    import json

    from mingpt_distributed_tpu.control.controller import CONTROL_SCHEMA
    from mingpt_distributed_tpu.trafficlab import (
        render_traffic_report,
        run_sweep,
        validate_traffic_report,
    )
    from mingpt_distributed_tpu.trafficlab.report import dump_report

    cfg, params = _tiny_model()
    spec = selftest_controller_spec()
    mix = selftest_mix()

    def run_once():
        logs = {}
        report = run_sweep(
            params, cfg, spec, mix=mix,
            control_log_sink=lambda r, lb, text: logs.__setitem__(
                (r, lb), text))
        return report, logs

    report, logs = run_once()
    print(render_traffic_report(report))

    rc = 0

    def check(ok: bool, what: str) -> None:
        nonlocal rc
        print(f"selftest-controller {'OK' if ok else 'FAIL'}: {what}")
        if not ok:
            rc = 1

    parsed = json.loads(dump_report(report))
    problems = validate_traffic_report(parsed, strict=False)
    check(not problems, f"report strict-parses (problems={problems})")
    check(parsed["policies"] == ["fifo", "fifo+auto"],
          f"cell labels carry the controller axis ({parsed['policies']})")

    rung = parsed["rungs"][0]
    static_cell = rung["policies"]["fifo"]
    auto_cell = rung["policies"]["fifo+auto"]
    control = auto_cell.get("control") or {}
    rep_actions = (control.get("actions") or {}).get("replicas", {})
    check(rep_actions.get("up", 0) >= 1,
          f"controller scaled up (replica actions={rep_actions})")
    check(rep_actions.get("down", 0) >= 1,
          f"controller scaled back down (replica actions={rep_actions})")

    s_hit, a_hit = (static_cell["deadline_hit_rate"],
                    auto_cell["deadline_hit_rate"])
    check(s_hit is not None and a_hit is not None and a_hit > s_hit,
          f"autoscaled beats static on deadline hit-rate "
          f"(auto={a_hit} static={s_hit})")
    s_cost, a_cost = static_cell["cost"]["cost"], auto_cell["cost"]["cost"]
    check(a_cost < s_cost,
          f"autoscaled cell is cheaper under the cost model "
          f"(auto={a_cost:.6g} static={s_cost:.6g})")

    log_text = logs.get((0, "fifo+auto"), "")
    rows = [json.loads(line) for line in log_text.splitlines()]
    check(bool(rows) and all(r.get("schema") == CONTROL_SCHEMA
                             for r in rows),
          f"control log is valid {CONTROL_SCHEMA} JSONL ({len(rows)} rows)")
    check(control.get("ticks") == len(rows),
          f"cell ticks match log rows ({control.get('ticks')} vs "
          f"{len(rows)})")

    report2, logs2 = run_once()
    check(dump_report(report) == dump_report(report2),
          "same-seed rerun report is byte-identical")
    check(logs == logs2, "same-seed rerun control log is byte-identical")

    print("selftest-controller " + ("PASSED" if rc == 0 else "FAILED"))
    return rc


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    if args.selftest_traffic:
        return selftest_traffic(args)
    if args.selftest_controller:
        return selftest_controller(args)

    from mingpt_distributed_tpu.config import load_config
    from mingpt_distributed_tpu.trafficlab import (
        render_traffic_report,
        run_sweep,
    )
    from mingpt_distributed_tpu.trafficlab.report import dump_report
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    spec = _sweep_spec(args)
    cfg = load_config(args.config, args.overrides)
    gpt_cfg = dataclasses.replace(
        cfg.gpt_config,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
    ).resolved()
    if args.random_init:
        import jax

        from mingpt_distributed_tpu.models import gpt

        params = gpt.init(jax.random.key(0), gpt_cfg)
        print(f"random-init model at {gpt_cfg.n_layer}L/"
              f"{gpt_cfg.n_embd}d (no checkpoint)", file=sys.stderr)
    else:
        import jax

        from mingpt_distributed_tpu.data.token_dataset import make_dataset
        from mingpt_distributed_tpu.training import checkpoint as ckpt_lib

        dataset = make_dataset(cfg.data_config)
        gpt_cfg = dataclasses.replace(
            gpt_cfg, vocab_size=dataset.vocab_size,
            block_size=dataset.block_size)
        path = (cfg.trainer_config.snapshot_path
                or ckpt_lib.DEFAULT_SNAPSHOT_PATH)
        snap = ckpt_lib.restore_inference_params(path, gpt_cfg)
        if snap is None:
            print(f"no snapshot at {path}; train first or pass "
                  f"--random-init", file=sys.stderr)
            return 1
        params = jax.device_put(snap.params)
        print(f"loaded snapshot step {snap.step} from {path}",
              file=sys.stderr)

    report = run_sweep(params, gpt_cfg, spec)
    print(render_traffic_report(report))
    if args.out is not None:
        with open(args.out, "w") as f:
            f.write(dump_report(report))
        print(f"mingpt-traffic/1 report written to {args.out}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
