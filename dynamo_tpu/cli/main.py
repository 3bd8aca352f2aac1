"""dynamo-tpu CLI: run/serve/store/models.

Analogue of the reference's launch binaries (reference:
launch/dynamo-run/src/{lib.rs:45-278, opt.rs:23-216, flags.rs:1-205} —
the in×out matrix; launch/llmctl — model registration ctl;
components/http — standalone frontend).

  dynamo-tpu run --in {http|text|stdin|batch:F|dyn://NS.COMP.EP} \
                 --out {echo_core|echo_full|jax|pystr:F|dyn://NS.COMP.EP|subproc:CMD} \
                 [--model-path DIR] [--model-name NAME] ...

  dynamo-tpu store            # run the coordinator (replaces etcd+NATS)
  dynamo-tpu models list      # ≈ llmctl
"""

from __future__ import annotations

import argparse
import asyncio
import atexit
import logging
import os
import sys
from typing import Any, Optional

from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.logging import init_logging
from dynamo_tpu.utils.tasks import spawn

log = logging.getLogger("dynamo_tpu.cli")

DYN_SCHEME = "dyn://"


from dynamo_tpu.runtime.component import parse_dyn_path  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dynamo-tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="run an input×output engine pairing")
    run.add_argument("--in", dest="in_mode", default="http",
                     help="http | text | stdin | batch:FILE.jsonl | "
                          "dyn://ns.comp.ep (serve as worker)")
    run.add_argument("--out", dest="out_mode", default="echo_full",
                     help="echo_core | echo_full | jax | pystr:FILE.py | "
                          "dyn://ns.comp.ep | subproc:CMD (spawn CMD as "
                          "a child engine that registers on a generated "
                          "{endpoint}; placeholders {endpoint} "
                          "{store_host} {store_port} {model_path} "
                          "{model_name} are substituted)")
    run.add_argument("--batch-output", default=None,
                     help="output path for --in batch: (default "
                          "INPUT.output.jsonl)")
    run.add_argument("--model-path", default=None,
                     help="local model directory (tokenizer/config/weights)")
    run.add_argument("--model-name", default=None)
    run.add_argument("--http-host", default="0.0.0.0")
    run.add_argument("--http-port", type=int, default=8000)
    run.add_argument("--store-host", default=None)
    run.add_argument("--store-port", type=int, default=None)
    run.add_argument("--static", action="store_true",
                     help="single-process mode: no coordinator needed")
    run.add_argument("--max-tokens-default", type=int, default=None)
    # engine knobs (reference: flags.rs)
    run.add_argument("--quantization", default=None, choices=["int8"],
                     help="weight-only quantization applied at load "
                          "(halves weight HBM traffic)")
    run.add_argument("--remote-kv-bucket", default="",
                     help="G4 KV tier: bucket in the coordinator's "
                          "object plane shared across workers "
                          "(requires --host-kv-blocks > 0)")
    run.add_argument("--decode-steps", type=int, default=1,
                     help="fused decode window: tokens per device "
                          "dispatch (amortizes dispatch latency; tokens "
                          "stream in bursts of this size)")
    run.add_argument("--spec-decode", default="",
                     help="speculative decoding drafter (needs "
                          "--decode-steps 1): ngram[:N] = prompt-lookup "
                          "self-drafting, bigram:PATH = static table; "
                          "empty disables (docs/speculative_decoding.md)")
    run.add_argument("--spec-tokens", type=int, default=4,
                     help="max draft tokens verified per sequence per "
                          "step (K); each decode step then emits 1..K+1 "
                          "tokens per sequence")
    run.add_argument("--prewarm-guided", action="store_true",
                     help="prewarm the guided-decoding (allow-mask) "
                          "step variants (needs --decode-steps 1): "
                          "keeps structured-output traffic free of "
                          "mid-serve compiles (docs/guided_decoding.md)")
    run.add_argument("--no-overlap", action="store_true",
                     help="disable the overlapped decode pipeline "
                          "(docs/performance.md): restores the fully "
                          "serial plan -> dispatch -> sync -> emit step "
                          "loop. Escape hatch + A/B baseline; greedy "
                          "output is bit-identical either way")
    run.add_argument("--mixed-prefill-rows", type=int, default=8,
                     help="mixed continuous batching (needs "
                          "--decode-steps > 1): pending prefill chunks "
                          "ride the decode window's dispatch in a fixed "
                          "[rows, len] rectangle; 0 disables")
    run.add_argument("--mixed-prefill-len", type=int, default=256,
                     help="per-row token cap of the mixed prefill "
                          "rectangle")
    run.add_argument("--mixed-prefill-wide-len", type=int, default=1024,
                     help="adaptive WIDE mixed rectangle: at low decode "
                          "occupancy the mixed window swaps to "
                          "[rows*len/wide_len, wide_len] (same token "
                          "budget, fewer rows) so long prompts stop "
                          "trickling at --mixed-prefill-len per window; "
                          "0 disables")
    run.add_argument("--mixed-wide-max-running", type=int, default=None,
                     help="decode-occupancy ceiling for the wide "
                          "rectangle (default: none — the wide and "
                          "narrow rectangles cost the same padded "
                          "budget, so the swap is free at any "
                          "occupancy when few prompts are prefilling)")
    run.add_argument("--tpu-chips", type=int, default=None, metavar="ID",
                     help="the one chip of this host to confine this "
                          "process to (e.g. 2), so several one-chip "
                          "workers can share a multi-chip host; unset, "
                          "the process takes every chip (tp>1)")
    run.add_argument("--tensor-parallel-size", type=int, default=1)
    run.add_argument("--pipeline-parallel-size", type=int, default=1,
                     help="GPipe stage rotation over a pp mesh axis")
    run.add_argument("--sequence-parallel-size", type=int, default=1,
                     help="prefill role only: shard the prompt over an "
                          "sp mesh axis (ring attention)")
    run.add_argument("--sp-attn", default="ring", choices=["ring", "ulysses"])
    # multimodal (vision-language) serving
    run.add_argument("--vision-config", default=None,
                     help="VisionConfig JSON: enables image_url content "
                          "parts (ViT encode + embedding injection)")
    run.add_argument("--vision-weights", default=None,
                     help=".npz vision tower weights (default: random)")
    run.add_argument("--image-token", default="<image>",
                     help="placeholder token for image patches")
    run.add_argument("--num-nodes", type=int, default=1)
    run.add_argument("--node-rank", type=int, default=0)
    run.add_argument("--leader-addr", default="")
    run.add_argument("--extra-engine-args", default=None,
                     help="JSON file with engine-specific settings")
    run.add_argument("--router-mode", default="round_robin",
                     choices=["random", "round_robin", "kv"])
    # disaggregated prefill/decode (reference: docs/disagg_serving.md)
    run.add_argument("--role", default="decode", choices=["decode", "prefill"],
                     help="worker role when disaggregation is enabled")
    run.add_argument("--disagg", action="store_true",
                     help="decode workers ship long prefills to the queue")
    run.add_argument("--namespace", default="dynamo",
                     help="namespace for prefill-role workers (no --in)")
    run.add_argument("--max-local-prefill-length", type=int, default=512)
    run.add_argument("--max-prefill-queue-size", type=int, default=16)
    run.add_argument("--advertise-host", default="127.0.0.1",
                     help="address prefill workers use to reach this "
                          "worker's KV transfer server")
    # robustness (docs/robustness.md: deadlines + load shedding; fault
    # injection is enabled via the DYN_FAULTS env var, never a flag)
    run.add_argument("--default-deadline-ms", type=float, default=None,
                     help="deadline budget applied to requests without "
                          "an X-Request-Timeout-Ms header; expired "
                          "requests are cancelled at every stage "
                          "(queue, prefill, decode) and their KV blocks "
                          "freed (default: no deadline)")
    run.add_argument("--shed-queue-depth", type=int, default=0,
                     help="admission control: reject requests 429 + "
                          "Retry-After when the engine's queue depth "
                          "(waiting + prefilling) reaches this "
                          "(--in http with a local engine; 0 disables)")
    run.add_argument("--shed-kv-usage", type=float, default=0.0,
                     help="admission control: shed when the device KV "
                          "pool usage fraction reaches this (e.g. 0.95; "
                          "0 disables)")
    run.add_argument("--drain-timeout-s", type=float, default=None,
                     help="graceful-drain budget for worker mode: on "
                          "SIGTERM (or a worker.drain control call) "
                          "in-flight streams are handed off to healthy "
                          "peers and the worker exits 0 once idle or "
                          "this deadline passes (default: "
                          "DYN_DRAIN_TIMEOUT_S, else 30)")
    # observability (docs/observability.md: SLO + flight recorder)
    run.add_argument("--slo-ttft-ms", type=float, default=None,
                     help="TTFT target evaluated per finished request "
                          "(engine-side submit -> first token); feeds "
                          "dynamo_slo_attainment / "
                          "dynamo_goodput_tokens_total")
    run.add_argument("--slo-itl-ms", type=float, default=None,
                     help="mean inter-token-latency target per request")
    run.add_argument("--slow-step-ms", type=float, default=None,
                     help="slow-step watchdog: a device step longer "
                          "than this dumps the flight-recorder ring to "
                          "JSONL (default: DYN_SLOW_STEP_MS, else off)")
    run.add_argument("--flight-recorder-steps", type=int, default=256,
                     help="flight-recorder ring capacity (last N engine "
                          "steps kept for /debug/state + anomaly dumps; "
                          "0 disables)")
    run.add_argument("--flight-dump-dir", default="",
                     help="where flight-recorder JSONL dumps land "
                          "(default: DYN_FLIGHT_DIR or the tmp dir)")
    # KV offload tiers
    run.add_argument("--subproc-ready-timeout", type=float, default=1800.0,
                     help="startup budget for --out subproc: children "
                          "(a real engine's cold prewarm compiles a "
                          "dozen step variants)")
    run.add_argument("--host-kv-blocks", type=int, default=0)
    run.add_argument("--disk-kv-blocks", type=int, default=0)
    run.add_argument("--disk-kv-path", default="")

    store = sub.add_parser("store", help="run the coordinator store")
    store.add_argument("--host", default="0.0.0.0")
    store.add_argument("--port", type=int, default=4222)
    store.add_argument("--native", action="store_true",
                       help="run the C++ coordinator (native/store; built "
                            "on demand, wire-identical to the python one)")
    store.add_argument("--persist-path", default=None,
                       help="durability: WAL + snapshot at this path — "
                           "model registrations, queues, and the object "
                           "plane survive a coordinator restart, incl. a "
                           "hard kill (leased liveness keys stay "
                           "ephemeral, like etcd). Both servers append "
                           "each acked mutation to a flushed WAL "
                           "(process-crash durable; host/power-crash "
                           "durability needs --fsync-wal on the native "
                           "server) and fold it into snapshots")
    store.add_argument("--fsync-wal", action="store_true",
                       help="(--native) fsync every WAL record before "
                            "acking: power-loss durable, like etcd's "
                            "raft-log fsync, at per-op fsync cost")

    serve = sub.add_parser("serve", help="serve a @service graph "
                           "(≈ reference `dynamo serve`)")
    serve.add_argument("service", nargs="?", default=None,
                       help="module:Attr of the entry DynamoService")
    serve.add_argument("--package", default=None,
                       help="serve a pushed package instead: name[:version]")
    serve.add_argument("-f", "--config-file", default=None,
                       help="YAML/JSON per-component overrides")
    serve.add_argument("--store-host", default="127.0.0.1")
    serve.add_argument("--store-port", type=int, default=4222)

    build = sub.add_parser("build", help="package a @service graph into a "
                           "versioned artifact (≈ reference `dynamo build`)")
    build.add_argument("service", help="module:Attr of the entry DynamoService")
    build.add_argument("--name", default=None,
                       help="package name (default: entry attr, lowered)")
    build.add_argument("-f", "--config-file", default=None,
                       help="YAML per-component overrides to embed")
    build.add_argument("--deployment-spec", default=None,
                       help="GraphDeploymentSpec YAML to embed")
    build.add_argument("-o", "--output", default=None,
                       help="archive path (default NAME-VERSION.tar.gz)")
    build.add_argument("--push", action="store_true",
                       help="push to the coordinator's package registry")
    build.add_argument("--store-host", default="127.0.0.1")
    build.add_argument("--store-port", type=int, default=4222)

    router = sub.add_parser("router", help="standalone KV-aware router "
                            "service (≈ reference components/router)")
    router.add_argument("--namespace", default="dynamo")
    router.add_argument("--component", default="backend",
                        help="worker component to route over")
    router.add_argument("--router-component", default="kv_aware_router",
                        help="component name this service registers as")
    router.add_argument("--block-size", type=int, default=16)
    router.add_argument("--store-host", default="127.0.0.1")
    router.add_argument("--store-port", type=int, default=4222)

    metrics = sub.add_parser("metrics", help="metrics aggregation service")
    metrics.add_argument("--namespace", default="dynamo")
    metrics.add_argument("--component", default="backend")
    metrics.add_argument("--port", type=int, default=9091)
    metrics.add_argument("--store-host", default="127.0.0.1")
    metrics.add_argument("--store-port", type=int, default=4222)

    planner = sub.add_parser("planner", help="autoscaling planner")
    planner.add_argument("--namespace", default="dynamo")
    planner.add_argument("--component", default="backend")
    planner.add_argument("--prefill-component", default="prefill")
    planner.add_argument("--metric-interval", type=float, default=5.0)
    planner.add_argument("--adjustment-interval", type=float, default=30.0)
    planner.add_argument("--min-decode", type=int, default=1)
    planner.add_argument("--max-decode", type=int, default=8)
    planner.add_argument("--min-prefill", type=int, default=0)
    planner.add_argument("--max-prefill", type=int, default=8)
    planner.add_argument("--grace-cycles", type=int, default=2,
                         help="consecutive breach cycles before acting")
    planner.add_argument("--slo-target", type=float, default=0.0,
                         help="scale decode up when slo_attainment_mean "
                              "stays below this (0 = watermark-only)")
    planner.add_argument("--slo-headroom", type=float, default=0.03,
                         help="extra attainment above --slo-target "
                              "required before scaling down")
    planner.add_argument("--reconcile-cycles", type=int, default=3,
                         help="adjustment cycles a worker may go missing "
                              "before reconciliation replaces it (0 = off)")
    planner.add_argument("--spawn-grace-cycles", type=int, default=10,
                         help="adjustment cycles an ordered worker may "
                              "take to start reporting before it is "
                              "presumed dead and replaced")
    planner.add_argument("--degrade-max-level", type=int, default=3,
                         help="graceful-degradation ladder ceiling "
                              "(0 disables the ladder)")
    planner.add_argument("--store-host", default="127.0.0.1")
    planner.add_argument("--store-port", type=int, default=4222)
    planner.add_argument("--log-dir", default=None,
                         help="write planner metrics JSONL (+ TensorBoard "
                              "events when torch is available) here")

    deploy = sub.add_parser("deploy", help="graph deployment ctl "
                            "(≈ DynamoGraphDeployment CRs)")
    deploy.add_argument("action",
                        choices=["apply", "status", "delete", "manifests"])
    deploy.add_argument("target", nargs="?",
                        help="spec YAML (apply/manifests) or deployment "
                             "name (delete)")
    deploy.add_argument("--namespace", default="dynamo")
    deploy.add_argument("--store-host", default="127.0.0.1")
    deploy.add_argument("--store-port", type=int, default=4222)
    deploy.add_argument("--image", default=None,
                        help="container image for generated manifests")
    deploy.add_argument("--output", "-o", default=None,
                        help="manifests: write YAML here (default stdout)")
    deploy.add_argument("--include-crd", action="store_true",
                        help="manifests: prepend the CRD definition")

    operator = sub.add_parser("operator", help="deployment reconciler "
                              "(≈ the K8s operator, local mode)")
    operator.add_argument("--namespace", default="dynamo")
    operator.add_argument("--interval", type=float, default=10.0)
    operator.add_argument("--api-port", type=int, default=8190,
                          help="api-store REST port (0 disables)")
    operator.add_argument("--store-host", default="127.0.0.1")
    operator.add_argument("--store-port", type=int, default=4222)
    operator.add_argument("--backend", default="local",
                          choices=["local", "kubectl"],
                          help="actuation: supervisor control subject "
                               "(local) or real cluster Deployments "
                               "(kubectl scale)")
    operator.add_argument("--k8s-namespace", default="default")
    operator.add_argument("--watch-k8s", action="store_true",
                          help="in-cluster mode: watch "
                          "DynamoGraphDeployment CRs via the k8s API "
                          "(kubectl) as the source of desired state and "
                          "write reconcile status back to each CR")
    operator.add_argument("--kubectl", default="kubectl",
                          help="kubectl binary for --backend=kubectl / "
                          "--watch-k8s")
    operator.add_argument("--state-dir", default=None,
                          help="persist applied specs here (survive "
                               "coordinator restarts)")

    # static analysis: `dynamo-tpu lint` (dynamo_tpu/analysis — dynalint)
    from dynamo_tpu.analysis.cli import add_lint_parser

    add_lint_parser(sub)

    # observability: `dynamo-tpu trace export` (dynamo_tpu/telemetry)
    trace = sub.add_parser(
        "trace", help="span-log tooling (DYN_TRACE_FILE JSONL)"
    )
    trace.add_argument("action", choices=["export"],
                       help="export: JSONL span logs -> Chrome-trace/"
                            "Perfetto JSON (open in ui.perfetto.dev)")
    trace.add_argument("files", nargs="+",
                       help="one or more DYN_TRACE_FILE JSONL logs "
                            "(one per process in a disaggregated fleet)")
    trace.add_argument("--output", "-o", default=None,
                       help="output path (default stdout)")
    trace.add_argument("--trace-id", default=None,
                       help="filter to one trace (id prefix is enough)")
    trace.add_argument("--rid", default=None,
                       help="filter to the trace(s) of one request id "
                            "(X-Request-Id) — resolved by scanning span "
                            "attrs; pairs with `dynamo-tpu autopsy`")

    # observability: `dynamo-tpu top` (live fleet view over /debug/state)
    top = sub.add_parser(
        "top", help="live fleet view: poll /debug/state and render a "
                    "terminal table (batch occupancy, KV usage, tok/s, "
                    "SLO attainment, HBM)"
    )
    top.add_argument("urls", nargs="*",
                     help="debug endpoint base URLs (default "
                          "http://127.0.0.1:8000); frontends and worker "
                          "metrics servers both qualify")
    top.add_argument("--interval", type=float, default=2.0,
                     help="seconds between polls")
    top.add_argument("--iterations", type=int, default=None,
                     help="stop after N frames (default: run forever)")
    top.add_argument("--once", action="store_true",
                     help="render one frame and exit")
    top.add_argument("--raw", action="store_true",
                     help="print JSON rows instead of the table")
    top.add_argument("--no-clear", action="store_true",
                     help="don't clear the screen between frames")

    # observability: `dynamo-tpu autopsy <rid>` (per-request timeline)
    autopsy_p = sub.add_parser(
        "autopsy", help="fetch one request's autopsy record "
                        "(/debug/request/{rid}) and render an ASCII "
                        "waterfall with a wall-clock coverage check"
    )
    autopsy_p.add_argument("rid", help="request id (X-Request-Id)")
    autopsy_p.add_argument("--url", default="http://127.0.0.1:8000",
                           help="frontend or metrics-server base URL")
    autopsy_p.add_argument("--json", action="store_true",
                           help="print the raw record instead of the "
                                "waterfall")

    models = sub.add_parser("models", help="model registry ctl (≈ llmctl)")
    models.add_argument("action", choices=["list", "register", "remove"])
    models.add_argument("name", nargs="?")
    models.add_argument("--model-path", help="local model dir (register)")
    models.add_argument("--endpoint", help="dyn://ns.comp.ep (register)")
    models.add_argument(
        "--model-type",
        default="chat_completion",
        choices=["chat", "completion", "chat_completion"],
    )
    models.add_argument("--store-host", default="127.0.0.1")
    models.add_argument("--store-port", type=int, default=4222)

    # lifecycle: `dynamo-tpu drain <worker>` (docs/robustness.md
    # "Graceful drain & rolling restarts")
    drain_p = sub.add_parser(
        "drain", help="gracefully drain a worker: it stops admitting, "
                      "hands in-flight streams to healthy peers, "
                      "deregisters, and exits 0"
    )
    drain_p.add_argument("worker",
                         help="instance id in hex (as shown by "
                              "`models list` or `top`)")
    drain_p.add_argument("--namespace", default="dynamo")
    drain_p.add_argument("--store-host", default="127.0.0.1")
    drain_p.add_argument("--store-port", type=int, default=4222)
    drain_p.add_argument("--timeout", type=float, default=45.0,
                         help="how long to wait for the worker to "
                              "deregister before giving up (exit 1)")
    return p


def _load_model_assets(args: Any):
    """Load tokenizer + optional chat template from --model-path."""
    from dynamo_tpu.preprocessor import PromptFormatter
    from dynamo_tpu.tokenizer import Tokenizer

    if not args.model_path:
        raise SystemExit(f"--out {args.out_mode} requires --model-path")
    if args.model_path.endswith(".gguf"):
        # GGUF single-file model: embedded tokenizer + chat template
        from dynamo_tpu.gguf import GGUFReader, tokenizer_from_gguf

        with GGUFReader(args.model_path) as r:
            tokenizer = tokenizer_from_gguf(r)
            template = r.metadata.get("tokenizer.chat_template")
            toks = r.metadata.get("tokenizer.ggml.tokens") or []

            def _tok_str(key: str) -> str:
                i = r.metadata.get(f"tokenizer.ggml.{key}")
                return toks[i] if i is not None and i < len(toks) else ""

            bos_str, eos_str = _tok_str("bos_token_id"), _tok_str("eos_token_id")
        formatter = None
        if template:
            try:
                formatter = PromptFormatter(
                    template, bos_token=bos_str, eos_token=eos_str
                )
            except Exception:
                log.warning("GGUF chat template failed to parse", exc_info=True)
        if formatter is None:
            log.warning("no chat template in GGUF; chat requests will fail")
    else:
        tokenizer = Tokenizer.from_file(args.model_path)
        try:
            formatter = PromptFormatter.from_model_dir(args.model_path)
        except Exception:
            formatter = None
            log.warning("no chat template found; chat requests will fail")
    from dynamo_tpu.model_card import default_model_name

    model_name = args.model_name or default_model_name(args.model_path)
    return tokenizer, formatter, model_name


def _wrap_pipeline(args: Any, core, eos_ids: list[int]):
    """preprocessor → backend → core engine."""
    from dynamo_tpu.backend import Backend
    from dynamo_tpu.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime.pipeline import build_pipeline

    tokenizer, formatter, model_name = _load_model_assets(args)
    if getattr(args, "vision_config", None):
        pre = _build_mm_preprocessor(args, tokenizer, formatter, model_name)
    elif _is_vlm_checkpoint(getattr(args, "model_path", None)):
        # REAL VLM checkpoint (LLaVA layout): tower + projector load
        # straight from the model dir, no --vision-config needed
        pre = _build_mm_preprocessor_from_checkpoint(
            args, tokenizer, formatter, model_name
        )
    else:
        pre = OpenAIPreprocessor(tokenizer, formatter, model_name=model_name)
    backend = Backend(tokenizer, eos_token_ids=eos_ids)
    from dynamo_tpu.preprocessor.fanout import ChoiceFanout

    # fanout sits between the preprocessor and the (backend -> engine)
    # tail: n>1 becomes n single-choice engine streams, each with its
    # own detokenizer/stop state, merged with choice indices
    return model_name, build_pipeline(
        pre, ChoiceFanout(build_pipeline(backend, core))
    )


def _build_mm_preprocessor(args: Any, tokenizer, formatter, model_name: str):
    """Vision-language pipeline head: ViT encode + placeholder splicing
    (reference: examples/multimodal encode worker + processor)."""
    import json

    from dynamo_tpu.models.vision import VisionConfig, load_vision_params

    with open(args.vision_config) as f:
        vcfg = VisionConfig.from_dict(json.load(f))
    vparams = None
    if args.vision_weights:
        vparams = load_vision_params(vcfg, args.vision_weights)
    else:
        log.warning("vision tower using RANDOM weights (no --vision-weights)")
    return _mm_preprocessor(
        args, tokenizer, formatter, model_name, vcfg, vparams, None
    )


def _mm_preprocessor(
    args: Any, tokenizer, formatter, model_name: str, vcfg, vparams,
    image_token_id,
):
    """Shared tail of both multimodal pipeline heads: encoder + token-id
    resolution + preprocessor wiring (one copy, two entry points)."""
    from dynamo_tpu.multimodal import MultimodalPreprocessor, VisionEncoder

    encoder = VisionEncoder(vcfg, params=vparams)
    if image_token_id is None:
        image_token_id = tokenizer.token_to_id(args.image_token)
    if image_token_id is None:
        raise SystemExit(
            f"tokenizer has no {args.image_token!r} token; pass --image-token"
        )
    return MultimodalPreprocessor(
        tokenizer,
        formatter,
        encode=encoder.encode_urls,
        image_token_id=int(image_token_id),
        tokens_per_image=encoder.tokens_per_image,
        model_name=model_name,
    )


def _is_vlm_checkpoint(model_path: Any) -> bool:
    """True when the model dir is a VLM checkpoint WE can serve
    multimodal: config.json carries a vision_config AND the weights use
    the LLaVA layout (vision_tower.vision_model.*). Other VLM layouts
    (Qwen2-VL, mllama, ...) fall back to text-only serving with a
    warning rather than crashing at startup."""
    import json

    if not model_path or not os.path.isdir(str(model_path)):
        return False
    cfg_path = os.path.join(str(model_path), "config.json")
    if not os.path.exists(cfg_path):
        return False
    try:
        with open(cfg_path) as f:
            if json.load(f).get("vision_config") is None:
                return False
        from dynamo_tpu.models.loader import _ShardedCheckpoint

        names = _ShardedCheckpoint(str(model_path)).names()
        if any(n.startswith("vision_tower.vision_model.") for n in names):
            return True
        log.warning(
            "%s has a vision_config but not the LLaVA weight layout; "
            "serving TEXT-ONLY (supported VLM layout: "
            "vision_tower.vision_model.* + multi_modal_projector.*)",
            model_path,
        )
        return False
    except Exception:
        return False


def _build_mm_preprocessor_from_checkpoint(
    args: Any, tokenizer, formatter, model_name: str
):
    """Vision-language pipeline head from a REAL VLM checkpoint: the
    tower + projector weights come from the model dir's safetensors
    (models/vision.py load_vision_hf); the image token id comes from
    the config's image_token_index (or the tokenizer)."""
    import json

    from dynamo_tpu.models.vision import load_vision_hf

    vcfg, vparams = load_vision_hf(args.model_path)
    with open(os.path.join(args.model_path, "config.json")) as f:
        raw = json.load(f)
    log.info(
        "VLM checkpoint: vision tower %d layers (feature-selected)",
        vcfg.num_hidden_layers,
    )
    return _mm_preprocessor(
        args, tokenizer, formatter, model_name, vcfg, vparams,
        raw.get("image_token_index"),
    )


async def _build_core_engine(args: Any):
    """The tokens-in/tokens-out core engine for out={echo_core,jax}.

    Returns (async_engine, eos_token_ids, jax_engine_or_None).
    """
    if args.out_mode == "echo_core":
        from dynamo_tpu.engines import EchoEngineCore

        return EchoEngineCore(), [], None
    try:
        from dynamo_tpu.engine import JaxEngine, load_engine_config
    except ImportError as exc:
        raise SystemExit(f"jax engine unavailable: {exc}")
    config = load_engine_config(args)
    engine = await JaxEngine.launch(config)
    return engine.as_async_engine(), engine.eos_token_ids, engine


async def _build_local_pipeline(args: Any):
    """Returns (model_name, pipeline, jax_engine_or_None) — the engine
    handle feeds frontend admission control when serving locally."""
    core, eos_ids, jax_engine = await _build_core_engine(args)
    name, pipeline = _wrap_pipeline(args, core, eos_ids)
    return name, pipeline, jax_engine


async def _connect_remote(
    args: Any, path: str, wait_timeout: Optional[float] = None, alive=None
):
    """Build the local pre/post pipeline around remote worker(s) at
    ``path``, behind a push router honoring --router-mode.

    ``wait_timeout`` None = DYN_DISCOVERY_TIMEOUT (default 300 s). The
    wait itself is event-driven (a store-prefix watch sets an
    asyncio.Event — runtime/component.py), so a generous budget costs
    nothing when workers are fast; the budget exists only to fail a
    fleet whose workers never come up. 30 s proved too tight for a
    worker that must JIT-compile its model while a loaded machine
    contends for cores (the r3/r4 full-suite discovery flakes — each
    passed isolated, timed out under load). ``alive``
    (optional) is polled while waiting for the first instance and may
    raise to abort early (the subproc adapter passes a child-process
    liveness check)."""
    import time as _time

    from dynamo_tpu.runtime.push_router import PushRouter, RouterMode
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    if wait_timeout is None:
        wait_timeout = float(os.environ.get("DYN_DISCOVERY_TIMEOUT", "300"))
    ns, comp, ep = parse_dyn_path(path)
    cfg = _runtime_config(args)
    drt = await DistributedRuntime.create(config=cfg)
    component = drt.namespace(ns).component(comp)
    client = await component.endpoint(ep).client()
    deadline = _time.monotonic() + wait_timeout
    while True:
        if alive is not None:
            alive()
        step = min(5.0, max(0.1, deadline - _time.monotonic()))
        try:
            await client.wait_for_instances(step)
            break
        except asyncio.TimeoutError:
            if _time.monotonic() >= deadline:
                raise
    if args.router_mode == "kv":
        from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter

        kv_router = await KvRouter.create(component, client)
        router = KvPushRouter(kv_router)
    else:
        mode = (
            RouterMode.ROUND_ROBIN
            if args.router_mode == "round_robin"
            else RouterMode.RANDOM
        )
        router = PushRouter(client, mode)
    # remote workers speak PreprocessedRequest: wrap with local pre/post
    return _wrap_pipeline(args, router, [])


async def cmd_run(args: Any) -> None:
    from dynamo_tpu.http.service import HttpService, ModelManager

    out = args.out_mode
    in_mode = args.in_mode
    worker_mode = in_mode.startswith(DYN_SCHEME)

    if args.role == "prefill":
        await _run_prefill_worker(args)
        return
    if args.disagg and not worker_mode:
        raise SystemExit("--disagg applies to workers (--in dyn://...)")

    # ---- output side: build the engine -----------------------------------
    jax_engine = None
    if out in ("echo_core", "jax"):
        if worker_mode:
            # workers serve the core tokens-in/tokens-out engine; pre/post
            # runs at the frontend (reference: subprocess engine pattern)
            model_name = args.model_name or "worker"
            engine, _, jax_engine = await _build_core_engine(args)
        else:
            model_name, engine, jax_engine = await _build_local_pipeline(args)
    elif out == "echo_full":
        from dynamo_tpu.engines import EchoEngineFull

        model_name = args.model_name or "echo"
        engine = EchoEngineFull()
    elif out.startswith("pystr:"):
        # user python file hosted as a text-in/text-out engine
        from dynamo_tpu.engines import PythonStrEngine

        path = out[len("pystr:"):]
        model_name = args.model_name or os.path.splitext(os.path.basename(path))[0]
        engine = PythonStrEngine(path)
    elif out.startswith(DYN_SCHEME):
        # remote worker(s) behind a push router
        model_name, engine = await _connect_remote(args, out)
    elif out.startswith("subproc:"):
        # subprocess engine adapter (reference: launch/dynamo-run/src/
        # subprocess.rs — spawn the engine as a child process that
        # connects BACK over the endpoint plane, then serve through it;
        # the reference embeds vllm/sglang python scripts this way).
        # The command line may reference {endpoint}, {store_host},
        # {store_port}, {model_path}, {model_name}; the same values are
        # exported as DYN_SUBPROC_* env vars. Anything able to serve
        # PreprocessedRequest -> LLMEngineOutput on the endpoint plane
        # qualifies — e.g.:
        #   --out "subproc:python -m dynamo_tpu.cli.main run
        #          --in {endpoint} --out jax --model-path {model_path}
        #          --store-port {store_port}"
        import shlex
        import subprocess

        ep_path = f"{DYN_SCHEME}internal.subproc{os.getpid()}.generate"
        # resolve the store address the way the parent itself connects
        # (flags > env > config file > defaults) — raw args would hand
        # the child port "0" whenever the flag is omitted
        _rt_cfg = _runtime_config(args)
        subs = {
            "endpoint": ep_path,
            "store_host": _rt_cfg.store_host,
            "store_port": str(_rt_cfg.store_port),
            "model_path": args.model_path or "",
            "model_name": args.model_name or "",
        }
        cmdline = out[len("subproc:"):]

        def _sub(token: str) -> str:
            # targeted placeholder substitution (str.format would choke
            # on unrelated braces, e.g. inline JSON engine args)
            for k, v in subs.items():
                token = token.replace("{" + k + "}", v)
            return token

        argv = [_sub(a) for a in shlex.split(cmdline)]
        env = dict(
            os.environ,
            **{f"DYN_SUBPROC_{k.upper()}": v for k, v in subs.items()},
        )
        child = subprocess.Popen(argv, env=env)
        print(f"subprocess engine: pid={child.pid} endpoint={ep_path}",
              flush=True)

        def _reap_child() -> None:
            if child.poll() is None:
                child.terminate()
                try:
                    child.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    child.kill()

        atexit.register(_reap_child)
        # SIGTERM's default action skips atexit — convert it to a normal
        # exit so the child engine is reaped when the adapter is stopped
        import signal as _sig

        def _on_term(signum, frame):
            _reap_child()
            sys.exit(0)

        for _s in (_sig.SIGTERM, _sig.SIGINT):
            try:
                _sig.signal(_s, _on_term)
            except (ValueError, OSError):
                pass  # non-main thread or unsupported platform
        def _child_alive() -> None:
            if child.poll() is not None:
                raise SystemExit(
                    f"subprocess engine exited during startup "
                    f"(rc={child.returncode})"
                )

        try:
            # startup budget covers a real engine's cold prewarm
            # (a dozen step variants of the whole model)
            model_name, engine = await _connect_remote(
                args, ep_path,
                wait_timeout=args.subproc_ready_timeout,
                alive=_child_alive,
            )
        except BaseException:
            _reap_child()
            raise
    elif out == "auto":
        # discovery-driven frontend: serve whatever models workers register
        # (reference: components/http standalone frontend + ModelWatcher)
        if in_mode != "http":
            raise SystemExit("--out auto requires --in http")
        from dynamo_tpu.http.discovery import ModelWatcher
        from dynamo_tpu.runtime.runtime import DistributedRuntime

        drt = await DistributedRuntime.create(config=_runtime_config(args))
        drt.runtime.install_signal_handlers()
        manager = ModelManager()
        # no local engine -> no load signal, so caps can't bind here
        # (deadlines still propagate to workers over the endpoint wire)
        # — but the planner's degradation ladder can: rung 3 sheds this
        # frontend to the probe trickle via force_shed
        if args.shed_queue_depth or args.shed_kv_usage:
            log.warning(
                "--shed-* flags need a local jax engine for load "
                "signals; load-based admission control disabled"
            )
        from dynamo_tpu.http.admission import (
            AdmissionConfig,
            AdmissionController,
        )
        from dynamo_tpu.planner.degradation import (
            ServingDegradation,
            watch_degradation,
        )

        admission = AdmissionController(
            AdmissionConfig(
                max_queue_depth=args.shed_queue_depth,
                max_kv_usage=args.shed_kv_usage,
            ),
            load_fn=lambda: None,  # fail open until the ladder says shed
        )
        # routers built by the watcher report migration resumes through
        # admission.check(resume=True) — never shed, but on the books
        watcher = ModelWatcher(
            drt, manager, router_mode=args.router_mode, admission=admission
        )
        await watcher.start()
        spawn(
            watch_degradation(
                drt.store, args.namespace,
                ServingDegradation(admission=admission),
            ),
            name="degradation-watch",
        )
        service = HttpService(
            manager, host=args.http_host, port=args.http_port,
            admission=admission,
            default_deadline_ms=args.default_deadline_ms,
        )
        await service.start()
        print(f"listening on http://{args.http_host}:{service.port}", flush=True)
        await drt.runtime.wait_shutdown()
        await watcher.close()
        await service.stop()
        await drt.shutdown()
        return
    else:
        raise SystemExit(f"unknown --out {out!r}")

    # ---- input side ------------------------------------------------------
    if in_mode == "http":
        manager = ModelManager()
        manager.add_chat_model(model_name, engine)
        manager.add_completion_model(model_name, engine)
        admission = None
        if (args.shed_queue_depth or args.shed_kv_usage) and jax_engine is not None:
            from dynamo_tpu.http.admission import (
                AdmissionConfig,
                AdmissionController,
                engine_load_fn,
            )

            admission = AdmissionController(
                AdmissionConfig(
                    max_queue_depth=args.shed_queue_depth,
                    max_kv_usage=args.shed_kv_usage,
                ),
                engine_load_fn(jax_engine),
                on_shed=jax_engine.slo.note_shed,
            )
            print(
                f"admission control: queue<{args.shed_queue_depth or '-'} "
                f"kv<{args.shed_kv_usage or '-'}", flush=True,
            )
        elif args.shed_queue_depth or args.shed_kv_usage:
            log.warning(
                "--shed-* flags need a local jax engine for load "
                "signals; admission control disabled"
            )
        service = HttpService(
            manager, host=args.http_host, port=args.http_port,
            admission=admission,
            default_deadline_ms=args.default_deadline_ms,
        )
        await service.start()
        print(f"listening on http://{args.http_host}:{service.port}", flush=True)
        # SIGTERM/SIGINT end the wait: stop accepting, then release the
        # engine (and with it the chip) before the process exits 0
        import signal

        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await service.stop()
        if jax_engine is not None:
            await jax_engine.shutdown()
    elif in_mode == "text":
        await _interactive_text(engine, model_name)
    elif in_mode == "stdin":
        await _stdin_once(engine, model_name, args.max_tokens_default)
    elif in_mode.startswith("batch:"):
        await _batch_file(engine, model_name, in_mode[len("batch:"):],
                          args.batch_output, args.max_tokens_default)
    elif in_mode.startswith(DYN_SCHEME):
        # worker mode: serve the core engine on an endpoint
        from dynamo_tpu.runtime.runtime import DistributedRuntime

        ns, comp, ep = parse_dyn_path(in_mode)
        cfg = _runtime_config(args)
        drt = await DistributedRuntime.create(config=cfg)
        drt.runtime.install_signal_handlers()
        component = drt.namespace(ns).component(comp)
        endpoint = component.endpoint(ep)
        if args.disagg:
            if jax_engine is None:
                raise SystemExit("--disagg requires --out jax (worker mode)")
            from dynamo_tpu.disagg.protocols import DisaggConfig
            from dynamo_tpu.disagg.worker import DisaggDecodeEngine

            jax_engine.refuse_kv_transfer()  # a family may not build it
            engine = await DisaggDecodeEngine.create(
                jax_engine,
                drt.store,
                ns,
                worker_id=drt.primary_lease_id,
                lease_id=drt.primary_lease_id,
                conf=DisaggConfig(
                    enabled=True,
                    max_local_prefill_length=args.max_local_prefill_length,
                    max_prefill_queue_size=args.max_prefill_queue_size,
                ),
                advertise_host=args.advertise_host,
            )
            print("disaggregation enabled (decode role)", flush=True)
        # KV event + load-metrics publication must be wired BEFORE the
        # instance becomes discoverable, or blocks cached in the window
        # between serve() and wiring never reach the router's index
        if jax_engine is not None:
            from dynamo_tpu.kv_router.publisher import (
                KvEventPublisher,
                KvMetricsPublisher,
            )

            kv_pub = KvEventPublisher(
                component,
                worker_id=drt.primary_lease_id,
                block_size=jax_engine.config.block_size,
            )
            jax_engine.kv_event_sink = kv_pub.sink
            metrics_pub = KvMetricsPublisher(
                component, drt.primary_lease_id, jax_engine.stats
            )
            metrics_pub.start()
            if (
                getattr(args, "remote_kv_bucket", "")
                and jax_engine.kvbm is not None
                and hasattr(jax_engine.kvbm, "attach_remote")
                # multihost ShardedKvOffload has no remote tier
            ):
                # G4 remote tier rides the coordinator's object plane.
                # attach via executor: the initial index refresh blocks
                # on THIS loop (calling it here would deadlock)
                from dynamo_tpu.kvbm.remote import StoreObjectAdapter

                adapter = StoreObjectAdapter(
                    drt.store, args.remote_kv_bucket,
                    asyncio.get_running_loop(),
                )
                await asyncio.get_running_loop().run_in_executor(
                    None, jax_engine.kvbm.attach_remote, adapter
                )
        if jax_engine is not None:
            # planner degradation ladder (docs/autoscaling.md): follow
            # the published rung; rung 2+ suspends spec decode here
            from dynamo_tpu.planner.degradation import (
                ServingDegradation,
                watch_degradation,
            )

            spawn(
                watch_degradation(
                    drt.store, ns, ServingDegradation(engine=jax_engine)
                ),
                name="degradation-watch",
            )
        instance = await endpoint.serve(engine)
        if args.model_path and args.model_path.endswith(".gguf"):
            # ModelDeploymentCard artifacts (tokenizer.json etc.) come
            # from model directories; a GGUF worker would register a
            # card discovery frontends can't build a pipeline from
            log.warning(
                "GGUF models are not registered for discovery frontends; "
                "serve them with a local pipeline (--in http) instead"
            )
        elif args.model_path and out in ("echo_core", "jax"):
            # publish the deployment card + this instance's ModelEntry so
            # discovery-driven frontends (--out auto) pick the model up
            # (reference: register_llm / llmctl http add). Only core
            # (PreprocessedRequest) engines register: that's the contract
            # discovery frontends build their pipelines against.
            from dynamo_tpu.model_card import default_model_name, register_llm

            await register_llm(
                drt.store,
                args.model_path,
                args.model_name or default_model_name(args.model_path),
                in_mode,
                drt.primary_lease_id,
            )
        print(f"worker serving {in_mode}", flush=True)
        # lifecycle (docs/robustness.md "Graceful drain"): a
        # worker.drain control call converges onto the same shutdown
        # event SIGTERM sets; either way the drain runs before the
        # lease is revoked, so departure is planned, not discovered
        from dynamo_tpu.runtime.drain import (
            DrainCoordinator,
            serve_drain_control,
        )

        spawn(
            serve_drain_control(drt, ns, instance, drt.runtime),
            name="drain-control",
        )
        await drt.runtime.wait_shutdown()
        await DrainCoordinator(
            drt, component, endpoint, instance,
            engine=jax_engine,
            timeout_s=args.drain_timeout_s,
        ).drain()
        await drt.shutdown()
    else:
        raise SystemExit(f"unknown --in {in_mode!r}")


async def _run_prefill_worker(args: Any) -> None:
    """Dedicated prefill worker: consumes the namespace's prefill queue
    (reference: examples/llm/components/prefill_worker.py)."""
    from dynamo_tpu.disagg.worker import run_prefill_worker
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    if args.out_mode != "jax":
        raise SystemExit("--role prefill requires --out jax")
    ns = (
        parse_dyn_path(args.in_mode)[0]
        if args.in_mode.startswith(DYN_SCHEME)
        else args.namespace
    )
    if getattr(args, "sequence_parallel_size", 1) > 1:
        await _run_sp_prefill_worker(args, ns)
        return
    _, _, jax_engine = await _build_core_engine(args)
    assert jax_engine is not None
    jax_engine.refuse_kv_transfer()  # a family may not build it
    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    print(f"prefill worker consuming {ns}_prefill_queue", flush=True)
    shutdown = asyncio.Event()

    async def _watch_shutdown() -> None:
        await drt.runtime.wait_shutdown()
        shutdown.set()

    watcher = spawn(_watch_shutdown(), name="cli-shutdown-watch")
    await run_prefill_worker(jax_engine, drt.store, ns, shutdown)
    watcher.cancel()
    await jax_engine.shutdown()
    await drt.shutdown()


async def _run_sp_prefill_worker(args: Any, ns: str) -> None:
    """Sequence-parallel prefill worker: the prompt shards over an sp
    mesh with ring/Ulysses attention (parallel/long_context.py) and the
    resulting KV blocks ship over the normal disagg transfer plane."""
    import jax

    from dynamo_tpu.disagg.worker import run_prefill_worker
    from dynamo_tpu.engine import load_engine_config
    from dynamo_tpu.models import loader
    from dynamo_tpu.parallel.long_context import LongContextPrefiller
    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    ecfg = load_engine_config(args)
    sp = args.sequence_parallel_size
    mesh = build_mesh(MeshConfig(sp=sp), jax.devices()[:sp])
    mc, params = loader.resolve_model(
        ecfg.model_path, random_weights=ecfg.random_weights, seed=ecfg.seed
    )
    prefiller = LongContextPrefiller(
        mc, params, mesh, block_size=ecfg.resolve_block_size(),
        attn=args.sp_attn, kv_dtype=ecfg.wire_kv_dtype(),
    )
    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    print(
        f"sp-prefill worker (sp={sp}, {args.sp_attn}) consuming "
        f"{ns}_prefill_queue",
        flush=True,
    )
    shutdown = asyncio.Event()

    async def _watch_shutdown() -> None:
        await drt.runtime.wait_shutdown()
        shutdown.set()

    watcher = spawn(_watch_shutdown(), name="cli-shutdown-watch")
    await run_prefill_worker(prefiller, drt.store, ns, shutdown)
    watcher.cancel()
    await drt.shutdown()


async def _interactive_text(engine: Any, model_name: str) -> None:
    """REPL chat (reference: dynamo-run in=text)."""
    from dynamo_tpu.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.engine import Context

    messages: list[dict] = []
    print(f"chatting with {model_name}; /clear resets, ctrl-d exits", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except (EOFError, KeyboardInterrupt):
            return
        if not line.strip():
            continue
        if line.strip() == "/clear":
            messages.clear()
            continue
        messages.append({"role": "user", "content": line})
        req = ChatCompletionRequest.model_validate(
            {"model": model_name, "messages": messages, "stream": True}
        )
        reply_parts: list[str] = []
        async for chunk in engine.generate(req, Context()):
            for choice in chunk.choices:
                if choice.delta.content:
                    reply_parts.append(choice.delta.content)
                    print(choice.delta.content, end="", flush=True)
        print()
        messages.append({"role": "assistant", "content": "".join(reply_parts)})


async def _stdin_once(engine: Any, model_name: str,
                      max_tokens: Optional[int] = None) -> None:
    """Read all of stdin as one prompt, stream the completion, exit
    (reference: dynamo-run in=stdin)."""
    from dynamo_tpu.protocols.openai import CompletionRequest
    from dynamo_tpu.runtime.engine import Context

    loop = asyncio.get_running_loop()
    prompt = await loop.run_in_executor(None, sys.stdin.read)
    if not prompt.strip():
        raise SystemExit("empty prompt on stdin")
    body = {"model": model_name, "prompt": prompt, "stream": True}
    if max_tokens is not None:
        body["max_tokens"] = max_tokens
    req = CompletionRequest.model_validate(body)
    async for chunk in engine.generate(req, Context()):
        for choice in chunk.choices:
            if choice.text:
                print(choice.text, end="", flush=True)
    print()


async def _batch_file(engine: Any, model_name: str, path: str,
                      out_path: Optional[str],
                      max_tokens: Optional[int]) -> None:
    """Run a JSONL batch of prompts and write responses + timings
    (reference: dynamo-run in=batch: — input/batch.rs; lines are
    {"text": ...}, output lines add response/tokens/latency)."""
    import json
    import time

    from dynamo_tpu.protocols.openai import CompletionRequest
    from dynamo_tpu.runtime.engine import Context

    with open(path) as f:
        prompts = [json.loads(line) for line in f if line.strip()]
    if not prompts:
        raise SystemExit(f"no prompts in {path}")
    for i, entry in enumerate(prompts):
        if not isinstance(entry, dict) or not isinstance(entry.get("text"), str):
            raise SystemExit(
                f"{path} line {i + 1}: expected {{\"text\": \"...\"}}"
            )
    out_path = out_path or path + ".output.jsonl"
    sem = asyncio.Semaphore(32)

    async def one(i: int, entry: dict) -> dict:
        async with sem:
            # clock starts only once a slot is held: timings report engine
            # latency, not client-side queue wait
            body = {"model": model_name, "prompt": entry["text"], "stream": True}
            if max_tokens is not None:
                body["max_tokens"] = max_tokens
            req = CompletionRequest.model_validate(body)
            parts: list[str] = []
            n_chunks = 0
            t0 = time.monotonic()
            t_first = None
            async for chunk in engine.generate(req, Context()):
                for choice in chunk.choices:
                    if choice.text:
                        if t_first is None:
                            t_first = time.monotonic()
                        parts.append(choice.text)
                        n_chunks += 1
            t1 = time.monotonic()
        return {
            "index": i,
            "text": entry["text"],
            "response": "".join(parts),
            "chunks": n_chunks,
            "ttft_ms": round(((t_first or t1) - t0) * 1000, 1),
            "total_ms": round((t1 - t0) * 1000, 1),
        }

    t0 = time.monotonic()
    results = await asyncio.gather(
        *[one(i, e) for i, e in enumerate(prompts)],
        return_exceptions=True,
    )
    wall = time.monotonic() - t0
    n_err = 0
    with open(out_path, "w") as f:
        for i, r in enumerate(results):
            if isinstance(r, BaseException):
                n_err += 1
                r = {"index": i, "text": prompts[i]["text"], "error": str(r)}
            f.write(json.dumps(r) + "\n")
    done = [r for r in results if not isinstance(r, BaseException)]
    total_chunks = sum(r["chunks"] for r in done)
    print(
        f"batch done: {len(done)}/{len(results)} prompts "
        f"({n_err} errors), {total_chunks} chunks, "
        f"{wall:.2f}s -> {out_path}",
        flush=True,
    )
    if n_err:
        raise SystemExit(1)


def _exec_native_store(args: Any) -> None:
    """Replace this process with the C++ coordinator (building it first
    if needed); falls through to the python server on build failure."""
    import importlib.util
    import socket

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    build_py = os.path.join(repo, "native", "build.py")
    binary = os.path.join(repo, "dynamo_tpu", "native", "dynamo_store")
    if not os.path.exists(binary) and os.path.exists(build_py):
        spec = importlib.util.spec_from_file_location("native_build", build_py)
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
            mod.build_store()  # errors print to stderr inside
        except Exception:
            log.warning("native store build failed", exc_info=True)
    if os.path.exists(binary):
        # the binary only accepts numeric addresses (inet_pton falls back
        # to INADDR_ANY): resolve hostnames here so --host localhost stays
        # loopback-only
        try:
            host = socket.gethostbyname(args.host)
        except OSError:
            raise SystemExit(f"cannot resolve --host {args.host!r}")
        argv = [binary, "--host", host, "--port", str(args.port)]
        if getattr(args, "persist_path", None):
            argv += ["--persist-path", args.persist_path]
        if getattr(args, "fsync_wal", False):
            argv += ["--fsync-wal"]
        os.execv(binary, argv)
    if getattr(args, "fsync_wal", False):
        raise SystemExit(
            "--fsync-wal needs the native store binary, which is "
            "unavailable; refusing to silently serve with the python "
            "server's weaker (flush-only) WAL durability"
        )
    log.warning("native store binary unavailable; using the python server")


def _runtime_config(args: Any) -> RuntimeConfig:
    overrides: dict[str, Any] = {}
    if getattr(args, "static", False):
        overrides["static"] = True
    if getattr(args, "store_host", None):
        overrides["store_host"] = args.store_host
    if getattr(args, "store_port", None):
        overrides["store_port"] = args.store_port
    return RuntimeConfig.from_settings(**overrides)


async def cmd_build(args: Any) -> None:
    """Package a graph (reference: sdk/cli/bentos.py build + push)."""
    import sys

    from dynamo_tpu.deploy.build import build_package, push_package

    sys.path.insert(0, os.getcwd())
    deployment = None
    if args.deployment_spec:
        from dynamo_tpu.deploy import GraphDeploymentSpec

        deployment = GraphDeploymentSpec.from_yaml_file(
            args.deployment_spec
        ).to_dict()
    path, manifest = build_package(
        args.service, name=args.name, config_file=args.config_file,
        deployment_spec=deployment, out_path=args.output,
    )
    print(f"built {manifest.name}:{manifest.version} -> {path} "
          f"({len(manifest.files)} files)")
    if args.push:
        from dynamo_tpu.store.client import StoreClient

        client = await StoreClient.connect(args.store_host, args.store_port)
        try:
            await push_package(client, path)
            print(f"pushed {manifest.name}:{manifest.version}")
        finally:
            await client.close()


async def cmd_serve(args: Any) -> None:
    """Supervise a @service graph (reference: cli/serving.py:163-300)."""
    import importlib

    from dynamo_tpu.sdk.service import DynamoService
    from dynamo_tpu.sdk.serving import Supervisor
    from dynamo_tpu.store.client import StoreClient

    from dynamo_tpu.sdk.runner import load_service

    if args.package:
        # pull + verify + unpack, then serve the embedded entry
        import sys

        from dynamo_tpu.deploy.build import pull_package, unpack_package

        name, _, version = args.package.partition(":")
        client = await StoreClient.connect(args.store_host, args.store_port)
        try:
            blob, version = await pull_package(client, name, version or None)
        finally:
            await client.close()
        dest_root = os.environ.get(
            "DYN_PACKAGE_DIR",
            os.path.join(os.path.expanduser("~"), ".dynamo_tpu", "packages"),
        )
        dest, manifest = unpack_package(blob, dest_root)
        src = os.path.join(dest, "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        # the supervisor's per-component CHILD processes import the
        # graph themselves: without this export they'd only find it if
        # the sources happened to be independently importable (e.g. a
        # repo checkout) — on a package-only machine they'd crash
        os.environ["PYTHONPATH"] = src + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""
        )
        # ...and for `-m` launches the child's CWD precedes PYTHONPATH
        # on sys.path, so a conflicting package under the operator's
        # working directory (a stale checkout) would silently shadow
        # the pulled artifact: serve from inside the package dir, which
        # contains no importable top-level packages
        os.chdir(dest)
        args.service = manifest.entry
        if not args.config_file and "config.yaml" in manifest.files:
            args.config_file = os.path.join(dest, "config.yaml")
        print(f"serving package {manifest.name}:{version} "
              f"(entry {manifest.entry})", flush=True)
    if not args.service:
        raise SystemExit("serve requires module:Attr or --package")
    entry = load_service(args.service)
    mod = importlib.import_module(args.service.partition(":")[0])
    specs = {
        obj.name: f"{mod.__name__}:{attr}"
        for attr, obj in vars(mod).items()
        if isinstance(obj, DynamoService)
    }
    overrides: dict[str, dict] = {}
    if args.config_file:
        with open(args.config_file) as f:
            text = f.read()
        try:
            import yaml

            overrides = yaml.safe_load(text) or {}
        except ImportError:
            import json as _json

            overrides = _json.loads(text)
    store = await StoreClient.connect(args.store_host, args.store_port)
    sup = Supervisor(
        entry=entry,
        store=store,
        namespace=entry.config.namespace,
        store_host=args.store_host,
        store_port=args.store_port,
        overrides=overrides,
        service_specs=specs,
    )
    await sup.start()
    print(f"serving graph {entry.name}: {list(specs)}", flush=True)
    stop = asyncio.Event()
    import signal as _signal

    loop = asyncio.get_running_loop()
    for sig in (_signal.SIGINT, _signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover
            pass
    await stop.wait()
    await sup.shutdown()
    await store.close()


async def cmd_router(args: Any) -> None:
    """Standalone KV-aware router: one shared index/scheduler multiple
    frontends consult (reference: components/router/src/main.rs:23-60 —
    the KvRouter served over an endpoint). Serves two endpoints on the
    router component:

      generate  — full proxy: requests stream through the chosen worker
      schedule  — decision only: {token_ids} -> {worker_id,
                  prefix_hit_rate, matched_blocks}; frontends dispatch
                  direct and share the index without proxy overhead
    """
    from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
    from dynamo_tpu.runtime.engine import AsyncEngine, Context, FnEngine
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    workers = drt.namespace(args.namespace).component(args.component)
    client = await workers.endpoint("generate").client()
    router = await KvRouter.create(workers, client, block_size=args.block_size)

    svc = drt.namespace(args.namespace).component(args.router_component)
    await svc.endpoint("generate").serve(KvPushRouter(router))

    async def schedule(request, ctx: Context):
        await client.wait_for_instances()
        decision = router.schedule(list(request["token_ids"]))
        yield {
            "worker_id": decision.worker_id,
            "prefix_hit_rate": decision.prefix_hit_rate,
            "overlap_blocks": decision.overlap_blocks,
            "total_blocks": decision.total_blocks,
        }

    await svc.endpoint("schedule").serve(FnEngine(schedule))
    print(
        f"kv router on dyn://{args.namespace}.{args.router_component}."
        f"{{generate,schedule}} over {args.component}",
        flush=True,
    )
    await drt.runtime.wait_shutdown()
    await router.close()
    await drt.shutdown()


async def cmd_metrics(args: Any) -> None:
    from dynamo_tpu.metrics.service import MetricsService
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    component = drt.namespace(args.namespace).component(args.component)
    svc = MetricsService(component, port=args.port)
    await svc.start()
    print(f"metrics on :{svc.port}/metrics", flush=True)
    await drt.runtime.wait_shutdown()
    await svc.close()
    await drt.shutdown()


async def cmd_planner(args: Any) -> None:
    from dynamo_tpu.planner.connector import LocalConnector
    from dynamo_tpu.planner.degradation import StoreDegradation
    from dynamo_tpu.planner.planner import Planner, PlannerConfig
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.utils import affinity

    # a planner process's event loop IS the planner domain (in-process
    # planners driven from tests stay on their host's "loop" binding)
    affinity.register_thread("planner")
    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    component = drt.namespace(args.namespace).component(args.component)
    planner = Planner(
        drt.store,
        component,
        LocalConnector(drt.store, args.namespace),
        # ladder rungs publish to the store; workers' watch_degradation
        # tasks apply them (admission caps, spec suspend)
        degradation=(
            StoreDegradation(drt.store, args.namespace)
            if args.degrade_max_level > 0
            else None
        ),
        config=PlannerConfig(
            decode_component=args.component,
            prefill_component=args.prefill_component,
            metric_interval_s=args.metric_interval,
            adjustment_interval_s=args.adjustment_interval,
            min_decode=args.min_decode,
            max_decode=args.max_decode,
            min_prefill=args.min_prefill,
            max_prefill=args.max_prefill,
            grace_cycles=args.grace_cycles,
            slo_target=args.slo_target,
            slo_headroom=args.slo_headroom,
            reconcile_cycles=args.reconcile_cycles,
            spawn_grace_cycles=args.spawn_grace_cycles,
            degrade_max_level=args.degrade_max_level,
        ),
    )
    mlog = None
    if args.log_dir:
        from dynamo_tpu.planner.metrics_log import MetricsLogger

        mlog = MetricsLogger(args.log_dir)
        planner.on_metrics = mlog
    try:
        await planner.start()
        print("planner running", flush=True)
        await drt.runtime.wait_shutdown()
        await planner.close()
    finally:
        if mlog is not None:
            mlog.close()  # flush buffered TensorBoard events
    await drt.shutdown()


async def cmd_deploy(args: Any) -> None:
    import json

    from dynamo_tpu.deploy import GraphDeploymentSpec, Reconciler
    from dynamo_tpu.store.client import StoreClient

    if args.action == "manifests":
        # offline: spec YAML -> real K8s objects, no store needed
        from dynamo_tpu.deploy.manifests import (
            DEFAULT_IMAGE,
            crd_manifest,
            graph_manifests,
            render_yaml,
            validate_k8s_doc,
        )

        if not args.target:
            raise SystemExit("deploy manifests requires a spec YAML path")
        spec = GraphDeploymentSpec.from_yaml_file(args.target)
        docs = graph_manifests(spec, image=args.image or DEFAULT_IMAGE)
        if args.include_crd:
            docs.insert(0, crd_manifest())
        for d in docs:
            validate_k8s_doc(d)
        text = render_yaml(docs)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text)
            print(f"wrote {len(docs)} manifests to {args.output}")
        else:
            print(text)
        return

    client = await StoreClient.connect(args.store_host, args.store_port)
    rec = Reconciler(client, args.namespace)
    try:
        if args.action == "apply":
            if not args.target:
                raise SystemExit("deploy apply requires a spec YAML path")
            spec = GraphDeploymentSpec.from_yaml_file(args.target)
            await rec.apply(spec)
            print(f"applied {spec.name} ({len(spec.services)} services)")
        elif args.action == "status":
            print(json.dumps(await rec.status(), indent=2))
        elif args.action == "delete":
            if not args.target:
                raise SystemExit("deploy delete requires a deployment name")
            if await rec.delete(args.target):
                print(f"deleted {args.target}")
            else:
                raise SystemExit(f"no deployment {args.target!r}")
    finally:
        await client.close()


async def cmd_operator(args: Any) -> None:
    from dynamo_tpu.deploy import ApiStore, Reconciler
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    drt = await DistributedRuntime.create(config=_runtime_config(args))
    drt.runtime.install_signal_handlers()
    factory = None
    if getattr(args, "backend", "local") == "kubectl":
        from dynamo_tpu.deploy.operator import KubectlConnector

        factory = lambda spec: KubectlConnector(  # noqa: E731
            spec.name, k8s_namespace=args.k8s_namespace,
            kubectl=getattr(args, "kubectl", "kubectl"),
        )
    rec = Reconciler(drt.store, args.namespace, interval_s=args.interval,
                     connector_factory=factory,
                     state_dir=getattr(args, "state_dir", None))
    if rec.state_dir:
        restored = await rec.restore_state()
        if restored:
            print(f"restored {restored} deployments from {rec.state_dir}",
                  flush=True)
    api = None
    if args.api_port:
        api = ApiStore(rec, port=args.api_port)
        await api.start()
        print(f"api-store on :{api.port}", flush=True)
    cr_task = None
    if getattr(args, "watch_k8s", False):
        from dynamo_tpu.deploy.operator import CrWatcher

        cr = CrWatcher(
            rec, k8s_namespace=args.k8s_namespace,
            kubectl=getattr(args, "kubectl", "kubectl"),
        )
        rec.on_results = cr.write_status
        print("watching DynamoGraphDeployment CRs (in-cluster mode)",
              flush=True)
    print("operator reconciling", flush=True)
    shutdown = asyncio.Event()

    async def _watch() -> None:
        await drt.runtime.wait_shutdown()
        shutdown.set()

    watcher = spawn(_watch(), name="operator-shutdown-watch")
    if getattr(args, "watch_k8s", False):
        cr_task = spawn(cr.run(shutdown), name="operator-cr-watch")
    await rec.run(shutdown)
    watcher.cancel()
    if cr_task is not None:
        cr_task.cancel()
    if api is not None:
        await api.stop()
    await drt.shutdown()


async def cmd_drain(args: Any) -> int:
    """Issue the worker.drain control call and poll discovery until the
    instance key disappears (the worker deletes it as its last act)."""
    from dynamo_tpu.runtime.drain import request_drain
    from dynamo_tpu.store.client import StoreClient

    client = await StoreClient.connect(args.store_host, args.store_port)
    try:
        print(f"draining {args.worker} in {args.namespace!r}...", flush=True)
        ok = await request_drain(
            client, args.namespace, args.worker, timeout_s=args.timeout
        )
    finally:
        await client.close()
    if ok:
        print(f"worker {args.worker} drained and deregistered")
        return 0
    print(f"worker {args.worker} still registered after {args.timeout}s "
          "(is it alive? did the control call reach it?)")
    return 1


async def cmd_models(args: Any) -> None:
    from dynamo_tpu.model_card import list_entries, register_llm, unregister_model
    from dynamo_tpu.store.client import StoreClient

    client = await StoreClient.connect(args.store_host, args.store_port)
    try:
        if args.action == "list":
            for entry in await list_entries(client):
                print(
                    f"{entry.name}\t{entry.model_type}\t{entry.endpoint}"
                    f"\tlease={entry.lease_id:x}"
                )
            instances = await client.kv_get_prefix("instances/")
            for e in instances:
                print(e.key)
        elif args.action == "register":
            # llmctl http add: manual registration for engines that don't
            # self-register (the card stays until `models remove`)
            if not (args.name and args.model_path and args.endpoint):
                raise SystemExit(
                    "models register requires NAME --model-path and --endpoint"
                )
            await register_llm(
                client,
                args.model_path,
                args.name,
                args.endpoint,
                lease_id=0,
                model_type=args.model_type,
            )
            print(f"registered {args.name} -> {args.endpoint}")
        elif args.action == "remove":
            if not args.name:
                raise SystemExit("models remove requires a name")
            n = await unregister_model(client, args.name)
            print(f"removed {n} entries")
    finally:
        await client.close()


def cmd_trace(args: Any) -> int:
    """Span-log export (pure file transform: no logging/jax setup)."""
    from dynamo_tpu.telemetry.export import export_chrome_trace

    # tolerate missing logs: a fleet role that never emitted a span
    # never creates its DYN_TRACE_FILE — warn and export the rest
    files = []
    for path in args.files:
        if os.path.exists(path):
            files.append(path)
        else:
            print(f"warning: no span log at {path}", file=sys.stderr)
    if not files:
        print("error: none of the span logs exist", file=sys.stderr)
        return 1
    trace_id = args.trace_id
    if getattr(args, "rid", None):
        if trace_id:
            print("error: --rid and --trace-id are mutually exclusive",
                  file=sys.stderr)
            return 1
        from dynamo_tpu.telemetry.export import trace_ids_for_request

        ids = trace_ids_for_request(files, args.rid)
        if not ids:
            print(f"error: no spans carry request_id={args.rid!r} "
                  "(was the frontend started with DYN_TRACE_FILE?)",
                  file=sys.stderr)
            return 1
        if len(ids) > 1:
            print(f"warning: rid {args.rid!r} matched {len(ids)} traces; "
                  f"exporting {ids[0]}", file=sys.stderr)
        trace_id = ids[0]
    if args.output:
        with open(args.output, "w") as f:
            n = export_chrome_trace(files, f, trace_id=trace_id)
        print(f"exported {n} spans -> {args.output}", file=sys.stderr)
    else:
        n = export_chrome_trace(files, sys.stdout, trace_id=trace_id)
        print(f"exported {n} spans", file=sys.stderr)
    return 0 if n else 1


def main(argv: Optional[list[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.command == "lint":
        # pure static analysis: no logging/jax setup, exit code gates CI
        from dynamo_tpu.analysis.cli import cmd_lint

        sys.exit(cmd_lint(args))
    if args.command == "trace":
        sys.exit(cmd_trace(args))
    if args.command == "top":
        # pure HTTP polling: no logging/jax setup
        from dynamo_tpu.cli.top import cmd_top

        sys.exit(cmd_top(args))
    if args.command == "autopsy":
        # one HTTP GET + terminal render: no logging/jax setup
        from dynamo_tpu.cli.autopsy import cmd_autopsy

        sys.exit(cmd_autopsy(args))
    init_logging()
    if getattr(args, "tpu_chips", None) is not None:
        # before anything can load libtpu: it takes what the
        # environment shows it when the first backend initialises
        from dynamo_tpu.sdk.allocator import chip_env

        os.environ.update(chip_env(args.tpu_chips))
    from dynamo_tpu.utils.jaxtools import configure_from_env

    configure_from_env()
    # deterministic fault injection (docs/robustness.md): DYN_FAULTS
    # activates a plan for THIS process; unset = every hook is a no-op
    from dynamo_tpu import faults

    faults.init_from_env()
    if args.command == "run":
        # every serving process keeps its newest request spans in memory:
        # a profiler capture writes them beside its trace
        from dynamo_tpu.telemetry import get_tracer

        get_tracer().keep_in_memory()
        try:
            asyncio.run(cmd_run(args))
        except KeyboardInterrupt:
            pass
    elif args.command == "store":
        if args.native:
            _exec_native_store(args)
        from dynamo_tpu.store.memory import MemoryStore
        from dynamo_tpu.store.server import StoreServer

        server = StoreServer(
            store=MemoryStore(persist_path=args.persist_path),
            host=args.host,
            port=args.port,
        )
        try:
            asyncio.run(server.serve_forever())
        except KeyboardInterrupt:
            pass
    elif args.command == "build":
        asyncio.run(cmd_build(args))
    elif args.command == "router":
        asyncio.run(cmd_router(args))
    elif args.command == "serve":
        try:
            asyncio.run(cmd_serve(args))
        except KeyboardInterrupt:
            pass
    elif args.command == "metrics":
        asyncio.run(cmd_metrics(args))
    elif args.command == "planner":
        asyncio.run(cmd_planner(args))
    elif args.command == "models":
        asyncio.run(cmd_models(args))
    elif args.command == "drain":
        sys.exit(asyncio.run(cmd_drain(args)))
    elif args.command == "deploy":
        asyncio.run(cmd_deploy(args))
    elif args.command == "operator":
        try:
            asyncio.run(cmd_operator(args))
        except KeyboardInterrupt:
            pass
    else:  # pragma: no cover
        sys.exit(2)


if __name__ == "__main__":
    main()
