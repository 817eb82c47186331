"""Batched serving example: jitted prefill + scan decode on a small LM.

Serves through the shared protocol in :mod:`repro.runtime.serving`:
prefill is one jitted chunked call, decode one jitted ``lax.scan``, and
``--prompts R`` pushes R ragged prompts through the fixed-slot batched
scheduler (``serve_requests``) — the production shape of the serve path.
Adding ``--continuous`` serves the same prompts through the overload-safe
continuous-batching engine under a seeded Poisson arrival trace
(``--rate`` requests/s) and prints each request's disposition and
latency — mid-stream admission does not change the greedy ids.

With ``--artifact`` the example serves a LayerMerge-COMPRESSED model: it
loads a portable merged-model artifact (written by ``python -m
repro.compress`` or ``CompressResult.save``), decodes through the shared
unit-graph executor (KV-cache aware — merged low-rank segments carry no
decode state at all), and reports compressed-vs-original throughput side
by side.

With ``--mesh`` the run shards over the host devices as a
('data','model') mesh (``--model-par`` picks the tensor-parallel split):
artifact weights are ``device_put`` straight to the shardings their
recorded logical axes resolve to, and the slot batch decodes
data-parallel.  Force multiple CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Run:  PYTHONPATH=src python examples/serve_lm.py [--tokens 32] [--batch 4]
      PYTHONPATH=src python -m repro.compress --arch smollm-135m \
          --budget-ratio 0.55 --out lm.npz
      PYTHONPATH=src python examples/serve_lm.py --artifact lm.npz \
          --prompts 8 --mesh
"""
import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh, mesh_info
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.runtime import serving
from repro.sharding.rules import make_unit_rules
from repro.train.step import make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--artifact", default=None,
                    help="merged-model artifact (.npz); serves the "
                         "compressed model and compares throughput")
    ap.add_argument("--prompts", type=int, default=0,
                    help="also serve N ragged prompts through the "
                         "fixed-slot batched scheduler")
    ap.add_argument("--continuous", action="store_true",
                    help="serve the --prompts trace through the "
                         "continuous-batching engine (Poisson arrivals)")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="arrival rate (requests/s) for --continuous")
    ap.add_argument("--mesh", action="store_true",
                    help="shard over the host devices (data × model)")
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="original-model init seed (overridden by the "
                         "artifact's recorded source seed)")
    args = ap.parse_args()

    rules = None
    if args.mesh:
        mesh = make_host_mesh(model=args.model_par)
        rules = make_unit_rules(mesh)
        print(f"[serve_lm] mesh {mesh_info(mesh)}")

    art = None
    if args.artifact:
        from repro import runtime

        art = runtime.load(args.artifact, rules=rules)
        if art.graph.family != "transformer":
            raise SystemExit("[serve_lm] --artifact must hold a "
                             "transformer-family graph")
        cfg = art.graph.meta["config"]
        seed = art.meta.get("source", {}).get("seed", args.seed)
        print(f"[serve_lm] artifact {args.artifact} "
              f"(fingerprint {art.fingerprint[:16]}, "
              f"oracle {art.meta.get('oracle')})")
    else:
        cfg = dataclasses.replace(
            get_config(args.arch).reduced(), num_layers=4, d_model=128,
            num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=512)
        seed = args.seed
    params, _ = T.init_model(cfg, jax.random.PRNGKey(seed))
    B, P = args.batch, args.prompt_len
    total = P + args.tokens
    prompt = serving.random_prompts(1, B, P, cfg.vocab_size)

    # original model: ONE chunked prefill call + one scan decode (the
    # shared jitted protocol; production prefill is the prefill_32k
    # dry-run cell)
    serve = make_serve_step(cfg)
    cache = T.init_cache(cfg, B, total)
    prefill_s, decode_s, _, seqs = serving.serve_loop(
        serve, params, cache, prompt, args.tokens, rules=rules)
    tps = serving.decode_tok_s(args.tokens - 1, B, decode_s)
    print(f"[serve_lm] batch={B} prompt={P} generated={args.tokens}")
    print(f"[serve_lm] original   prefill {prefill_s*1e3:.1f} ms, decode "
          f"{decode_s*1e3:.1f} ms ({tps:.0f} tok/s on this host)")

    if art is not None:
        ex = art.executor(rules)
        step, cparams = ex.serve_step()
        c_prefill_s, c_decode_s, _, cseqs = serving.serve_loop(
            step, cparams, ex.init_cache(B, total), prompt, args.tokens,
            rules=rules)
        ctps = serving.decode_tok_s(args.tokens - 1, B, c_decode_s)
        print(f"[serve_lm] compressed prefill {c_prefill_s*1e3:.1f} ms, "
              f"decode {c_decode_s*1e3:.1f} ms ({ctps:.0f} tok/s)")
        print(f"[serve_lm] decode speedup {decode_s / c_decode_s:.2f}x "
              f"(DP-predicted {art.meta.get('predicted_speedup', '?')}x)")
        print(f"[serve_lm] compressed continuation ids: "
              f"{cseqs[0, :12].tolist()}")

    if args.prompts:
        mat, lens = serving.pad_prompts(
            serving.ragged_prompts(2, args.prompts, min(4, P), P,
                                   cfg.vocab_size))
        if art is not None:
            bstep, bparams, mkcache = step, cparams, ex.init_cache
        else:
            bstep, bparams = serve, params
            mkcache = lambda b, s: T.init_cache(cfg, b, s)   # noqa: E731
        gen, secs = serving.serve_requests(
            bstep, bparams, mkcache, mat, lens, tokens=args.tokens,
            slots=B, rules=rules)
        btps = serving.decode_tok_s(args.tokens, args.prompts, secs)
        print(f"[serve_lm] scheduler: {args.prompts} ragged prompts in "
              f"{B}-slot rounds → {secs*1e3:.1f} ms ({btps:.0f} tok/s)")
        print(f"[serve_lm] slot-0 continuation ids: {gen[0, :12].tolist()}")
        if args.continuous:
            import numpy as np

            rng = np.random.RandomState(11)
            arrivals = [float(a) for a in np.cumsum(
                rng.exponential(1.0 / args.rate, size=args.prompts))]
            cgen, csecs = cout = serving.serve_continuous(
                bstep, bparams, mkcache, mat, lens, tokens=args.tokens,
                slots=B, rules=rules, arrivals=arrivals)
            rep = cout.report
            print(f"[serve_lm] continuous: {args.prompts} requests, "
                  f"Poisson rate {args.rate:g}/s, {B} slots → "
                  f"{csecs*1e3:.1f} ms wall "
                  f"({rep.sustained_tok_s:.0f} sustained tok/s, "
                  f"queue peak {rep.queue_peak})")
            for rid in sorted(rep.dispositions):
                lat = rep.latency_s.get(rid)
                lat_ms = "-" if lat is None else f"{lat*1e3:7.1f} ms"
                print(f"[serve_lm]   request {rid}: "
                      f"{rep.dispositions[rid]:<13s} latency {lat_ms}")
            same = bool(np.array_equal(np.asarray(cgen), np.asarray(gen)))
            print(f"[serve_lm] continuous ids == scheduler ids: {same}")
    print(f"[serve_lm] sample continuation ids: {seqs[0, :12].tolist()}")


if __name__ == "__main__":
    main()
