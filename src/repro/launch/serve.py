"""Serving launcher: ``python -m repro.launch.serve --arch llama3-8b --smoke
--mode lbim`` — request-level generation through the CD-PIM-mode engine.

The model is prepared ONCE (``ServingModel.prepare``: backend pinned, W8A8
weights pre-quantized under ``--quantized-decode``, cache layout fixed), then
every request rides its own ``GenerationRequest`` — budget, eos, sampling
(``--temperature/--top-k/--top-p/--seed``) and, with ``--stream``, a
streaming callback printing tokens as they emit. ``--shared-prefix N`` gives
every request an identical N-token system prompt so ``--prefix-cache`` (on
by default) demonstrates admission-time reuse; ``--no-prefix-cache``
disables it for an A/B schedule comparison. ``--faults SEED`` injects a
deterministic chaos plan (see ``repro.serve.faults``) and prints the
engine's post-run health snapshot; ``--ttft-deadline`` / ``--deadline``
bound each request in engine steps.

Traffic plane: ``--arrival-rate R`` drives the requests through a seeded
Poisson arrival process (mean R arrivals per engine step — requests become
visible to admission only when the engine clock reaches their arrival step),
``--trace FILE`` replays a saved ``serve.traffic`` trace instead, and
``--mode-policy auto`` installs the per-step SLO-aware LBIM/HBCEM policy in
place of the static ``--mode`` pin. Every run prints the latency summary —
TTFT/TPOT/queue-wait percentiles on the engine-step clock plus SLO
attainment when deadlines are declared.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.pim_modes import Mode, SloAwarePolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import traffic
from repro.serve.api import GenerationRequest, RequestState, SamplingParams
from repro.serve.faults import FaultPlan
from repro.serve.serving_model import ServingModel
from repro.serve.spec import SpecConfig


def main() -> str | None:
    """Run the launcher; returns an error message (the process's non-zero
    exit) when a request FAILED or the ladder degraded without ``--faults``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=[m.value for m in Mode], default="hbcem")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a slot the step it emits this token "
                         "(default: the arch config's eos_id)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (exact argmax); >0 samples")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus cutoff in (0, 1] (1 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed of each request's private RNG lane "
                         "(request i uses seed + i)")
    ap.add_argument("--quantized-decode", action="store_true",
                    help="route decode projections through the pre-quantized "
                         "W8A8 PIM-GEMV path (quantized at load)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="speculative decoding: prepare this arch as the "
                         "draft model (e.g. rwkv6-1.6b, or the target arch "
                         "itself for a self-draft acceptance ceiling)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft depth per verify round (with --spec-draft); "
                         "per-request spec_k can cap it further")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share content-hashed prompt-prefix blocks across "
                         "requests (skipped prefill tokens; on by default "
                         "where the cache family supports it)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical system-prompt tokens "
                         "to every request (demonstrates prefix reuse)")
    ap.add_argument("--stream", action="store_true",
                    help="print each token the step it is emitted")
    ap.add_argument("--faults", type=int, default=None, metavar="SEED",
                    help="inject a deterministic seeded FaultPlan (alloc "
                         "failures, kernel faults, NaN logits, slow steps) "
                         "and print the engine's health snapshot after")
    ap.add_argument("--ttft-deadline", type=int, default=None,
                    help="per-request first-token deadline in engine steps "
                         "(missed -> request times out, slot freed)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request total deadline in engine steps "
                         "(missed -> emitted tokens kept, finish=timeout)")
    ap.add_argument("--arrival-rate", type=float, default=None, metavar="R",
                    help="Poisson arrival process at mean R requests per "
                         "engine step (seeded by --seed; requests stay "
                         "invisible to admission until their arrival step)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="replay a saved serve.traffic trace file instead "
                         "of generating requests (overrides --arrival-rate)")
    ap.add_argument("--mode-policy", default=None,
                    choices=["auto"] + [m.value for m in Mode],
                    help="per-step mode policy: 'auto' = SLO-aware "
                         "LBIM/HBCEM choice each step; a mode name pins it "
                         "(equivalent to --mode)")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.quantized_decode:
        cfg = cfg.replace(quantized_decode=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.max_new + 8
    sm = ServingModel.prepare(cfg, params, slots=args.slots, max_len=max_len)
    dev = jax.devices()[0]
    print(f"prepared {cfg.name}: backend={sm.backend} "
          f"prequantized={sm.prequantized} device={dev.platform}/"
          f"{dev.device_kind} x{len(jax.devices())} compile_cache={cache_dir}")
    spec = None
    if args.spec_draft is not None:
        dcfg = get_config(args.spec_draft, smoke=args.smoke)
        dsm = (sm if dcfg.name == cfg.name else ServingModel.prepare(
            dcfg, M.init_params(jax.random.PRNGKey(1), dcfg),
            slots=args.slots, max_len=max_len))
        spec = SpecConfig(draft=dsm, k=args.spec_k)
        print(f"speculative decoding: draft={dcfg.name} k={args.spec_k}")

    if args.trace is not None or args.arrival_rate is not None:
        if args.trace is not None:
            trace = traffic.TrafficTrace.load(args.trace)
            print(f"traffic: replaying {len(trace.requests)} requests "
                  f"from {args.trace}")
        else:
            trace = traffic.generate(traffic.TrafficConfig(
                n_requests=args.requests, seed=args.seed,
                rate=args.arrival_rate,
                prompt_len=(args.prompt_len, args.prompt_len),
                max_new=(args.max_new, args.max_new),
                vocab=cfg.vocab_size,
                ttft_deadline=args.ttft_deadline, deadline=args.deadline))
            print(f"traffic: poisson rate={args.arrival_rate}/step "
                  f"seed={args.seed} ({len(trace.requests)} requests)")
        reqs = trace.to_requests()
    else:
        rng = np.random.default_rng(0)
        shared = list(map(int, rng.integers(1, cfg.vocab_size,
                                            args.shared_prefix)))
        reqs = []
        for i in range(args.requests):
            prompt = shared + list(map(int, rng.integers(1, cfg.vocab_size,
                                                         args.prompt_len)))
            on_token = (lambda t, i=i: print(f"  [stream] req{i} -> {t}",
                                             flush=True)) if args.stream else None
            reqs.append(GenerationRequest(
                prompt=prompt, max_new_tokens=args.max_new, eos_id=args.eos_id,
                sampling=SamplingParams(temperature=args.temperature,
                                        top_k=args.top_k, top_p=args.top_p,
                                        seed=args.seed + i),
                on_token=on_token,
                ttft_deadline=args.ttft_deadline, deadline=args.deadline))

    policy = None
    mode = Mode(args.mode)
    if args.mode_policy == "auto":
        policy = SloAwarePolicy()
    elif args.mode_policy is not None:
        mode = Mode(args.mode_policy)
    eng = sm.engine(mode=mode, chunk=args.chunk,
                    prefix_cache=args.prefix_cache, spec=spec,
                    step_policy=policy)
    if args.faults is not None:
        eng.fault_plan = FaultPlan.seeded(args.faults)
    t0 = time.perf_counter()
    results = eng.serve(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    rep = eng.schedule_report()
    mode_label = args.mode_policy or args.mode
    print(f"mode={mode_label} generated {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) schedule={rep.to_json()}")
    # latency + SLO summary ALWAYS (engine-step clock): the serving numbers
    # that matter under arrival-driven traffic
    lat = rep["latency"]
    ttft, tpot, qw = (lat["ttft_steps"], lat["tpot_steps"],
                      lat["queue_wait_steps"])
    print(f"latency (steps): "
          f"ttft p50={ttft.get('p50')} p95={ttft.get('p95')} "
          f"p99={ttft.get('p99')} | "
          f"tpot p50={tpot.get('p50')} p95={tpot.get('p95')} | "
          f"queue-wait p50={qw.get('p50')} p95={qw.get('p95')}")
    slo = lat.get("slo")
    if slo is not None:
        print(f"SLO attainment: {slo['met']}/{lat['requests']} "
              f"({slo['attainment']:.2%}; {slo['declared']} requests "
              f"declared deadlines) mode_steps={rep['mode_steps']}")
    if eng.prefix_cache:
        print(f"prefix cache: {rep['prefix']['prefix_hits']} hits / "
              f"{rep['prefix']['prefix_lookups']} lookups, "
              f"{rep['reused_prefix_tokens']} prefill tokens skipped")
    if spec is not None:
        sp = rep["spec"]
        print(f"spec: {sp['rounds']} rounds, accepted {sp['accepted']}/"
              f"{sp['proposed']} drafts (rate {sp['acceptance_rate']:.2f}), "
              f"{sp['draft_steps']} draft GEMV steps, "
              f"{sp['verify_tokens']} verify tokens")
    for i, r in enumerate(results[:3]):
        print(f"  req{i} ({r.state.value}/{r.finish_reason}): {r.tokens}")
    # post-run health + occupancy ALWAYS: a clean run prints its zeros,
    # which is exactly the evidence that nothing leaked or degraded
    h = eng.health()
    occ = h["occupancy"]
    print(f"health: degraded={h['degraded']} counters={h['counters']}")
    print(f"occupancy: slots {occ['slots_used']}/{occ['slots_total']} "
          f"pages {occ['pages_used']}/{occ['pages_total']} "
          f"prefix_pins={occ['prefix_pins']}")
    if args.faults is not None:
        print(f"ladder: {h['ladder']} fault_plan: {h['fault_plan']}")
    # a fallback outside a chaos run, or a failed request, is a failed run:
    # it would otherwise exit 0 while serving from a path nobody asked for
    failed = sum(r.state is RequestState.FAILED for r in results)
    if failed or (h["degraded"] and args.faults is None):
        return (f"serve failed: {failed} request(s) FAILED, "
                f"ladder={h['ladder']}")
    return None


if __name__ == "__main__":
    sys.exit(main())
