"""One-command compression + artifact export.

Runs the full pipeline (tables → DP → merge) on a named architecture and
publishes a portable merged-model artifact — no example-script surgery:

  PYTHONPATH=src python -m repro.compress --arch tiny_resnet \
      --budget-ratio 0.6 --out artifact.npz

  PYTHONPATH=src python -m repro.compress --arch smollm-135m \
      --budget-ratio 0.55 --out lm.npz
  PYTHONPATH=src python examples/serve_lm.py --artifact lm.npz

CNN archs come from :mod:`repro.models.zoo`; transformer archs resolve
through :func:`repro.configs.get_config` (reduced to the CPU-sized toy
variant unless ``--full``).  Parameters are seed-initialized — the CLI
demonstrates the plan→artifact path; a production run would restore
pre-trained params from a checkpoint before compressing.  The artifact
records the source (arch, seed, reduced) so consumers such as
``serve_lm --artifact`` can rebuild the matching original network for
side-by-side throughput numbers.
"""
from __future__ import annotations

import argparse
import json


CNN_ARCHS = {
    "tiny_resnet": lambda zoo: zoo.tiny_resnet(
        num_classes=4, in_hw=16, width=8, blocks=(2, 2)),
    "tiny_mobilenet": lambda zoo: zoo.tiny_mobilenet(
        num_classes=4, in_hw=16, width=8),
    "tiny_ddpm": lambda zoo: zoo.ddpm_unet(
        in_hw=16, ch=16, ch_mult=(1, 2), num_res_blocks=1,
        attn_resolutions=(8,), gn_groups=8),
    "resnet34": lambda zoo: zoo.resnet34(),
    "mobilenetv2": lambda zoo: zoo.mobilenetv2(),
    "ddpm_cifar10": lambda zoo: zoo.ddpm_unet(),
}


def build_host(arch: str, *, seed: int = 0, batch: int = 8, seq: int = 128,
               full: bool = False, max_span: int | None = None):
    """(host, source-dict) for a named CNN-zoo or transformer arch."""
    import jax

    key = jax.random.PRNGKey(seed)
    source = {"arch": arch, "seed": seed}
    if arch in CNN_ARCHS:
        from repro.models import cnn, cnn_host, zoo

        net = CNN_ARCHS[arch](zoo)
        params = cnn.init_params(net, key)
        host = cnn_host.CNNHost(net, params, batch=batch, max_span=max_span)
        source["family"] = "cnn"
        return host, source
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.transformer_host import CostEnv, TransformerHost

    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    params, _ = T.init_model(cfg, key)
    host = TransformerHost(cfg, params,
                           env=CostEnv(batch=batch, seq=seq),
                           max_span=max_span)
    source.update(family="transformer", reduced=not full)
    return host, source


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.compress",
        description="LayerMerge compression → merged-model artifact")
    ap.add_argument("--arch", required=True,
                    help=f"CNN zoo ({', '.join(CNN_ARCHS)}) or a "
                         "transformer config id (e.g. smollm-135m)")
    ap.add_argument("--budget-ratio", type=float, default=0.6)
    ap.add_argument("--method", default="layermerge",
                    choices=("layermerge", "depth", "layeronly"))
    ap.add_argument("--oracle", default="analytic",
                    choices=("analytic", "wallclock"),
                    help="wallclock times every probe on this device and "
                         "exits non-zero if any had to fall back to the "
                         "analytic estimate")
    ap.add_argument("--P", type=int, default=200,
                    help="latency discretization steps (Algorithm 1)")
    ap.add_argument("--quantize", default="none",
                    choices=("none", "int8", "w8a8"),
                    help="let the DP pick per-unit precision: widens the "
                         "tables with int8-weight (int8) or int8-weight+"
                         "activation (w8a8) candidates; chosen segments "
                         "lower to narrow-weight units (artifact v3)")
    ap.add_argument("--out", required=True, help="artifact path (.npz)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length for the transformer cost env")
    ap.add_argument("--max-span", type=int, default=None)
    ap.add_argument("--full", action="store_true",
                    help="transformer: full config, not .reduced()")
    ap.add_argument("--cache-dir", default=None,
                    help="lookup-table cache directory (optional)")
    ap.add_argument("--resume", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="resume an interrupted table build from its "
                         "write-ahead journal in --cache-dir (default on; "
                         "--no-resume discards a stale journal)")
    ap.add_argument("--probe-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="per-probe wall-clock budget; over-budget probes "
                         "retry, then quarantine to the analytic estimate")
    ap.add_argument("--probe-retries", type=int, default=2,
                    help="attempts per failing probe before quarantine")
    ap.add_argument("--workers", type=int, default=0,
                    help="fan latency probes out across N subprocess "
                         "workers with lease-based reassignment (requires "
                         "--cache-dir; tables stay bit-identical; refused "
                         "with --oracle wallclock on an accelerator host, "
                         "where this process holds the chip)")
    ap.add_argument("--work-dir", default=None,
                    help="shared coordination directory for --workers "
                         "(default: under --cache-dir)")
    args = ap.parse_args(argv)

    from repro.core import ProbeConfig, WallClockOracle, compress
    from repro.core.dist_build import DistBuildError
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    host, source = build_host(args.arch, seed=args.seed, batch=args.batch,
                              seq=args.seq, full=args.full,
                              max_span=args.max_span)
    oracle = WallClockOracle() if args.oracle == "wallclock" else None
    probe_config = ProbeConfig(timeout_s=args.probe_timeout,
                               retries=args.probe_retries)
    host_spec = {"factory": "repro.testing.hosts:cli_host",
                 "kwargs": {"arch": args.arch, "seed": args.seed,
                            "batch": args.batch, "seq": args.seq,
                            "full": args.full,
                            "max_span": args.max_span}}
    try:
        res = compress(host, budget_ratio=args.budget_ratio, P=args.P,
                       method=args.method, latency_oracle=oracle,
                       importance="magnitude", cache_dir=args.cache_dir,
                       probe_config=probe_config, resume=args.resume,
                       workers=args.workers, host_spec=host_spec,
                       work_dir=args.work_dir, quantize=args.quantize)
    except DistBuildError as e:
        print(f"[repro.compress] distributed build failed: {e}")
        raise SystemExit(3)
    if res is None:
        raise SystemExit(
            f"[repro.compress] infeasible: no plan fits "
            f"budget_ratio={args.budget_ratio} for {args.arch}")
    fp = res.save(args.out, extra_meta={"source": source})
    plan = res.plan
    summary = {
        "arch": args.arch,
        "method": args.method,
        "budget_ratio": args.budget_ratio,
        "layers": plan.num_layers,
        "kept_layers": len(plan.C),
        "segments": len(plan.segments),
        "predicted_speedup": round(res.speedup, 3),
        "quantize": args.quantize,
        "quantized_units": sum(1 for s in plan.segments
                               if s.quant != "none"),
        "flagged_probes": (len(res.tables.provenance)
                           if res.tables is not None else 0),
        "quarantined_probes": res.num_quarantined,
        "artifact": args.out,
        "fingerprint": fp[:16],
    }
    if res.dist_report is not None:
        rep = res.dist_report
        summary["dist"] = {"workers": rep.workers, "items": rep.items,
                           "reassigned": len(rep.reassigned),
                           "dead_workers": rep.dead_workers,
                           "cache_hit": rep.cache_hit}
    print(json.dumps(summary, indent=2))
    if oracle is not None and res.num_quarantined:
        # The plan rests on analytic stand-ins for probes that failed on
        # this device: the artifact is kept for inspection, the run fails.
        raise SystemExit(
            f"[repro.compress] {res.num_quarantined} wall-clock probe "
            f"bucket(s) failed and were quarantined to the analytic "
            f"estimate")


if __name__ == "__main__":
    main()
