"""Argparse config system.

Parity: reference ``src/{single,dp,ddp}/config.py`` ``load_config()``.  The
reference duplicates the parser per variant with small deltas (ckpt path,
epoch default, ddp-only distributed flags); here one parser serves every
backend, with the variant passed as ``backend`` by each entry point.

Flag mapping (reference → TPU-native):

====================  =====================================================
reference flag         meaning here
====================  =====================================================
``--amp``              bfloat16 compute policy (no GradScaler — TPU bf16
                       needs no loss scaling; ref ``src/single/main.py:14``)
``--workers``          host-side data workers for the streaming pipeline
                       (unused by the device-resident CIFAR path)
``--world-size``       number of JAX processes (hosts), for
                       ``jax.distributed.initialize``
``--rank``             this process's index among hosts
``--dist-url``         coordinator address for DCN rendezvous (analogue of
                       the reference's TCP store ``tcp://127.0.0.1:3456``,
                       ``src/ddp/config.py:25-26``)
``--dist-backend``     kept for CLI compatibility; on TPU the collective
                       fabric is ICI/DCN chosen by XLA, so the only value
                       is ``"xla"``
====================  =====================================================

Additional TPU-native flags are grouped at the bottom (mesh shape, precision,
synthetic data, resume) — capabilities the reference lacks but this framework
provides.
"""

from __future__ import annotations

import argparse
from typing import Sequence

# host-side prefetch depth (reference DataLoader num_workers default analogue)
WORKERS_DEFAULT = 4
# host data mode: loader steps scanned per device dispatch
HOST_CHUNK_STEPS_DEFAULT = 32
# staged device chunks in flight ahead of the running dispatch (HBM cap)
DEVICE_PREFETCH_DEFAULT = 2


def build_parser(backend: str = "single") -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description=f"dtc_tpu {backend} backend",
    )

    # default hparams (reference src/single/config.py:8-18)
    parser.add_argument("--dset", type=str, default="cifar100")
    parser.add_argument("--dpath", type=str, default="data/")
    parser.add_argument(
        "--ckpt-path", type=str, default=f"src/{backend}/checkpoints/"
    )
    parser.add_argument("--seed", type=int, default=42, help="Seed for reproducibility")
    parser.add_argument("--workers", type=int, default=WORKERS_DEFAULT)
    parser.add_argument("--eval-step", type=int, default=300)
    parser.add_argument(
        "--amp",
        action="store_true",
        default=False,
        help="bfloat16 compute policy (TPU-native AMP; no loss scaling needed)",
    )
    parser.add_argument("--contain-test", action="store_true", default=False)

    # distributed hparams (reference src/ddp/config.py:21-26)
    parser.add_argument(
        "--world-size", type=int, default=1, help="Total number of host processes"
    )
    parser.add_argument("--rank", type=int, default=0, help="This host's process index")
    parser.add_argument(
        "--dist-backend",
        type=str,
        default="xla",
        help="Collective backend; XLA emits ICI/DCN collectives (NCCL analogue)",
    )
    parser.add_argument(
        "--dist-url",
        default="127.0.0.1:3456",
        type=str,
        help="Coordinator address for jax.distributed.initialize",
    )

    # training hparams (reference src/ddp/config.py:29-37); the reference's
    # single variant defaults to 200 epochs, dp/ddp to 100
    # (src/single/config.py:21 vs src/ddp/config.py:29)
    parser.add_argument(
        "--epoch", type=int, default=200 if backend == "single" else 100
    )
    parser.add_argument("--batch-size", type=int, default=128, help="GLOBAL batch size")
    parser.add_argument(
        "--model",
        type=str,
        default="resnet18",
        choices=[
            "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
            "vit_tiny", "vit_small", "vit_long", "vit_moe",
            "lfm2_24b_a2b", "lfm2_tiny", "trinity_mini", "afmoe_tiny",
            "qwen3_next", "qwen3_next_tiny", "nemotron_h", "nemotron_h_tiny",
        ],
        help="Model zoo entry (live, unlike the reference's dead --model flag)",
    )
    parser.add_argument(
        "--model-cut",
        type=str,
        default=None,
        help="What this chip holds of a published token model "
        "(lfm2_*, trinity_mini, afmoe_tiny, qwen3_next*, nemotron_h*; "
        "models/token_parts.py), as layers=N,dense=N,experts=N,"
        "first_expert=N,vocab=N: layers kept (the leading dense ones, then "
        "the layers that follow them), experts held in every expert layer "
        "and the first one's index, vocabulary rows. A model without "
        "leading dense layers (qwen3_next*, nemotron_h*) takes dense=0 or no dense= at "
        "all. No width is cut; the router keeps every output. Keys left "
        "out keep the published value",
    )
    parser.add_argument(
        "--seq-len",
        type=int,
        default=4096,
        help="Tokens a sequence for a token model (one document a "
        "sequence, no packing; --batch-size counts sequences)",
    )
    parser.add_argument(
        "--optimizer",
        type=str,
        default="sgd",
        choices=["sgd", "adamw"],
        help="'sgd' = the paper's Nesterov SGD with coupled decay; 'adamw' "
        "= AdamW (beta 0.9/0.95, eps 1e-8, decoupled --weight-decay on "
        "matrices only), both under the StepLR schedule",
    )
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--weight-decay", type=float, default=0.0001)
    parser.add_argument("--lr-decay-step-size", type=int, default=60)
    parser.add_argument("--lr-decay-gamma", type=float, default=0.1)

    # TPU-native extensions (no reference equivalent)
    parser.add_argument(
        "--num-devices",
        type=int,
        default=0,
        help="Devices to use (0 = all local devices)",
    )
    parser.add_argument(
        "--model-parallel",
        type=int,
        default=1,
        help="Model-parallel mesh axis size; data-parallel size = "
        "num_devices / model_parallel. --parallel-style picks what the "
        "axis does (tensor vs pipeline parallelism)",
    )
    parser.add_argument(
        "--parallel-style",
        type=str,
        default="tensor",
        choices=["tensor", "pipeline", "sequence", "sequence-ulysses"],
        help="How the model axis is used when --model-parallel > 1: "
        "'tensor' = Megatron-style channel sharding (ResNet stages 3-4 + "
        "head, or the ViT trunk's q/k/v/proj/mlp pairs); 'pipeline' = GPipe "
        "microbatch pipeline over the stacked transformer trunk; "
        "'sequence' / 'sequence-ulysses' = shard the token axis across the "
        "trunk with ring attention / Ulysses all-to-all (vit_* models only)",
    )
    parser.add_argument(
        "--pipeline-parallel",
        type=int,
        default=1,
        help="Pipeline-parallel degree on the DEDICATED 'pipe' mesh axis "
        "(parallel/mesh.py): the stacked transformer trunk is staged "
        "across P pipeline stages, COMPOSABLE with --model-parallel "
        "tensor parallelism (DP x TP x PP — the trunk shards (pipe on "
        "the depth axis, model on the feature dims), so model size "
        "scales past one TP group's HBM). Requires a vit_* model and "
        "--parallel-style tensor (the model axis keeps its meaning). "
        "1 = off. --parallel-style pipeline remains the legacy "
        "single-axis spelling (pipe schedule on the model axis, no TP)",
    )
    parser.add_argument(
        "--pipeline-microbatches",
        type=int,
        default=0,
        help="Microbatches per step for pipeline parallelism "
        "(0 = auto: 4x the stage count; bubble fraction (P-1)/(M+P-1))",
    )
    parser.add_argument(
        "--pipeline-virtual-stages",
        type=int,
        default=0,
        help="Virtual stages per device for --pipeline-schedule "
        "interleaved (each device owns v NON-contiguous layer chunks; "
        "per-tick work shrinks v-fold so the warmup/cooldown bubble "
        "shrinks toward ((v+1)P-2)/(vM+(v+1)P-2) at the same microbatch "
        "count). 0 = auto: 2 for the interleaved schedule, 1 otherwise. "
        "Requires depth %% (P*v) == 0 and microbatches %% P == 0",
    )
    parser.add_argument(
        "--patch-size",
        type=int,
        default=0,
        help="ViT patch size override (0 = model default, e.g. 4). "
        "patch 2 at 32px quadruples the token count to 256 — the "
        "long-sequence regime on CIFAR inputs",
    )
    parser.add_argument(
        "--moe-dispatch",
        type=str,
        default="auto",
        choices=["auto", "gmm", "gather", "onehot"],
        help="MoE token-dispatch implementation (vit_moe): 'gmm' = fused "
        "Pallas grouped matmul over expert-sorted tokens (ops/moe_gmm.py, "
        "the TPU fast path; unsharded experts only); 'gather' = "
        "sort/scatter/gather, O(n*d) data movement, pure XLA (shards "
        "under expert parallelism); 'onehot' = GShard-style "
        "dispatch/combine matmuls, O(n*E*cap*d) MXU FLOPs (models/moe.py "
        "cost model); 'auto' (default) = gmm on TPU with unsharded "
        "experts, else gather",
    )
    parser.add_argument(
        "--block-fusion",
        type=str,
        default="auto",
        choices=["auto", "force", "off"],
        help="fused Pallas transformer-block kernel (vit_*, "
        "ops/vit_block.py): 'auto' = on TPU for dense blocks with "
        "128 <= tokens <= 512 (the measured win regime; composed "
        "automatically under tensor/pipeline model parallelism, where "
        "block params shard); 'off' = always the composed XLA path; "
        "'force' = fused even off-TPU through the Pallas interpreter "
        "(tests/debugging). NOTE 'force' still composes outside the "
        "128-512 token window, for MoE blocks, over the VMEM weight "
        "budget, and under sequence parallelism (the kernel has no "
        "sequence-sharded form) — a one-time warning names the declined "
        "condition; it only errors under tensor/pipeline model "
        "parallelism",
    )
    parser.add_argument(
        "--scan-unroll",
        type=int,
        default=0,
        help="ViT trunk lax.scan unroll factor: 0 = auto (full unroll on "
        "TPU, scanned elsewhere), -1 = full, N = unroll N blocks per scan "
        "iteration. Full unroll removes the scanned loop's per-layer "
        "residual stacking (measured ~1.9x on vit_tiny/bs256/bf16)",
    )
    parser.add_argument(
        "--pipeline-schedule",
        type=str,
        default="gpipe",
        choices=["gpipe", "1f1b", "interleaved"],
        help="Pipeline schedule: 'gpipe' = all forwards then all backwards "
        "(autodiff reverse; O(M) stashed microbatches per stage); '1f1b' = "
        "one-forward-one-backward with per-stage activation recompute "
        "(same bubble, O(P) stashed microbatches — the memory headroom "
        "that lets M grow); 'interleaved' = 1F1B over v virtual stages "
        "per device (--pipeline-virtual-stages): non-contiguous layer "
        "chunks cut the warmup/cooldown bubble ~v-fold at the same "
        "microbatch count, same O(P) stash",
    )
    parser.add_argument(
        "--pipeline-resident-layout",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Carry the trunk stack in the schedule's native layout "
        "(parallel/layouts.py): under --pipeline-schedule interleaved "
        "with virtual stages the TrainState holds the (v, P, K) chunk "
        "view, deleting the per-step relayout from the hot path "
        "(checkpoints stay canonical/contiguous on disk either way). "
        "--no-pipeline-resident-layout keeps the legacy per-step "
        "relayout (same trajectory: tests/test_layouts.py)",
    )
    parser.add_argument(
        "--precision",
        type=str,
        default=None,
        choices=["fp32", "bf16"],
        help="Compute precision; overrides --amp when set",
    )
    parser.add_argument(
        "--progress",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="tqdm progress bars (epoch bar always; step bar in host data "
        "mode), process-0 only — reference shows bars on every variant "
        "(src/single/trainer.py:126-130)",
    )
    parser.add_argument(
        "--bn-dtype",
        type=str,
        default="fp32",
        choices=["fp32", "compute"],
        help="Dtype BatchNorm reduces batch statistics in. 'fp32' (default) "
        "keeps mean/var reduction full-precision even under the bf16 policy "
        "— low-precision stat reduction is an accuracy risk; 'compute' "
        "reduces in the activation dtype",
    )
    parser.add_argument(
        "--synthetic-data",
        action="store_true",
        default=False,
        help="Train on generated data (benchmark mode / no dataset on disk)",
    )
    parser.add_argument(
        "--synthetic-noise",
        type=float,
        default=0.15,
        help="Noise sigma around the per-class anchor images of "
        "--synthetic-data. Higher = harder task; convergence-parity runs "
        "raise it so final accuracy lands mid-range instead of saturating",
    )
    parser.add_argument(
        "--remat",
        action="store_true",
        default=False,
        help="Rematerialize residual blocks on backward (jax.checkpoint): "
        "~1/3 extra FLOPs for a large cut in peak activation memory — "
        "enables batches/models that otherwise OOM",
    )
    parser.add_argument(
        "--shard-optim",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="ZeRO-style cross-replica sharding of the weight update "
        "(parallel/comms.py, arxiv 2004.13336): the optimizer state is "
        "carried sharded 1/N over the data axis and the update runs "
        "reduce-scatter(grads) → per-shard optimizer step → "
        "all-gather(params), expressed as sharding constraints so it "
        "composes with tensor/pipeline parallelism. Per-device "
        "optimizer-state HBM shrinks ~1/N (visible in the compile-event "
        "memory ledger); checkpoints stay bit-compatible — save/restore "
        "reshards through the host-pytree format",
    )
    parser.add_argument(
        "--grad-comms",
        type=str,
        default="fp32",
        choices=["fp32", "fp16", "int8"],
        help="Gradient-sync wire precision (parallel/comms.py): fp16/int8 "
        "quantize the gradient at the sync boundary with an error-feedback "
        "residual carried in the train state (compression noise feeds the "
        "NEXT step instead of being lost — the DynamiQ recipe). With "
        "--shard-optim the quantized payload is what crosses the "
        "reduce-scatter. fp32 (default) = uncompressed, executable "
        "unchanged",
    )
    parser.add_argument(
        "--grad-accum",
        type=int,
        default=1,
        help="Gradient accumulation: split each global batch into N "
        "sequential micro-batches, average their grads, apply ONE update. "
        "Reaches spec-scale global batches on few chips (BN statistics are "
        "per-micro-batch, like torch DDP without cross-step SyncBN)",
    )
    parser.add_argument(
        "--image-size",
        type=int,
        default=32,
        help="Synthetic image edge length (e.g. 224 with --stem imagenet "
        "for ImageNet-scale benchmarking)",
    )
    parser.add_argument(
        "--stem",
        type=str,
        default="cifar",
        choices=["cifar", "imagenet"],
        help="Model stem: 'cifar' = 3x3/1 conv, no maxpool (reference "
        "parity); 'imagenet' = 7x7/2 conv + 3x3/2 maxpool for large images",
    )
    parser.add_argument(
        "--limit-examples",
        type=int,
        default=0,
        help="Truncate each split to N examples (0 = full dataset); for "
        "smoke runs and CI",
    )
    parser.add_argument(
        "--valid-examples",
        type=int,
        default=0,
        help="Hold out exactly N of the train split's examples for "
        "validation (0 = the reference's tenth, rounded down)",
    )
    parser.add_argument(
        "--resume",
        type=str,
        default=None,
        help="Path to a last.ckpt to resume from (full train-state restore; "
        "capability absent in the reference — see SURVEY.md §5)",
    )
    parser.add_argument(
        "--auto-resume",
        action="store_true",
        default=False,
        help="Continue the newest interrupted run under --ckpt-path (its "
        "version dir + last.ckpt) if one exists; otherwise start fresh. "
        "The crash-restart flag: relaunch the same command after a "
        "failure and training picks up where it stopped",
    )
    parser.add_argument(
        "--save-last",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Also save a resumable last.ckpt each epoch (on top of the "
        "reference's best-only policy); --no-save-last for best-only",
    )
    parser.add_argument(
        "--log-every-step",
        action="store_true",
        default=False,
        help="Write a TensorBoard loss point for every step (reconstructed "
        "from the per-epoch loss fetch; no extra device syncs)",
    )
    parser.add_argument(
        "--save-last-every",
        type=int,
        default=1,
        help="Write the resumable last.ckpt every N epochs (1 = every epoch)",
    )
    parser.add_argument(
        "--save-last-min-secs",
        type=float,
        default=20.0,
        help="Throttle resumable-state saves to at most one per this many "
        "seconds (the device→host fetch of the full train state can cost "
        "more than a fast epoch's compute; the final epoch always saves). "
        "0 disables the throttle",
    )
    parser.add_argument(
        "--data-mode",
        type=str,
        default="device",
        choices=["device", "host"],
        help="'device': whole split HBM-resident, scanned epochs (fastest; "
        "CIFAR-scale). 'host': stream numpy batches per step with per-host "
        "sharding (datasets that don't fit in HBM / multi-host loaders)",
    )
    parser.add_argument(
        "--host-chunk-steps",
        type=int,
        default=HOST_CHUNK_STEPS_DEFAULT,
        help="host data mode: loader steps scanned per device dispatch "
        "(amortizes dispatch + H2D latency; the loss trajectory is "
        "identical for any value)",
    )
    parser.add_argument(
        "--device-chunk-steps",
        type=int,
        default=0,
        help="device data mode: steps per scanned dispatch (0 = whole "
        "epoch, the monolithic default — behavior unchanged). Smaller "
        "chunks give the health watchdog and the preemption poll "
        "chunk-boundary granularity mid-epoch; the trajectory is "
        "bit-identical for any value (the chunk recomputes the epoch "
        "permutation and per-step keys the monolithic program derives)",
    )
    parser.add_argument(
        "--device-prefetch",
        type=str,
        default=str(DEVICE_PREFETCH_DEFAULT),
        help="host data mode: staged device chunks the background H2D "
        "thread keeps in flight ahead of the running dispatch (bounds the "
        "extra HBM at N chunk buffers; transfer hides behind compute). "
        "0 = synchronous staging on the main thread (the pre-overlap "
        "path). 'auto' = derive the depth PER HOST from this host's free "
        "HBM headroom (parallel/planner.py auto_staging_depth) — a "
        "straggler host with less headroom stages shallower locally "
        "instead of stalling the collective dispatch at a fleet-global "
        "constant; backends without memory stats keep the default "
        f"({DEVICE_PREFETCH_DEFAULT})",
    )
    parser.add_argument(
        "--parallel-plan",
        type=str,
        default="off",
        choices=["off", "auto", "dump"],
        help="Ledger-fit auto-parallel planner (parallel/planner.py): "
        "enumerate DP×TP×PP(×virtual-stage)×--shard-optim×--grad-comms "
        "layouts, feasibility-filter through the existing gates, score "
        "with a cost model fit to the compile-event ledger under "
        "--ckpt-path, and 'auto' = install the fastest legal layout at "
        "trainer construction (overriding hand-picked layout flags; "
        "--grad-comms stays the numerics ceiling — the planner never "
        "compresses below what the flag authorized). 'dump' = score and "
        "log the candidate table but run the hand-picked flags. Every "
        "decision is one registered 'plan' event; run_report --plan "
        "renders prediction vs measured and fails a stream whose "
        "installed plan disagrees with the run_start layout. Under "
        "--supervise --fleet-hosts the supervisor re-plans at every "
        "attempt boundary, so a fleet resize lands on the fastest legal "
        "layout rather than the widest, and the autopilot's 'replan' "
        "policy action can force a fresh plan off an HBM-ledger alert",
    )
    parser.add_argument(
        "--ckpt-comms-residual",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="Checkpoint the --grad-comms error-feedback residual in "
        "last.ckpt (the manifest records its presence), so resume keeps "
        "the compression error the wire already dropped instead of "
        "restarting it at zero. Cross-flag restores (saved with, "
        "restoring without — or the wire layout changed) keep the "
        "documented drop-and-warn path; rollback always resets the "
        "residual (it belonged to the discarded trajectory). Off by "
        "default: the residual costs a params-sized fetch per save for "
        "at most one step's quantization error",
    )
    parser.add_argument(
        "--profile-dir",
        type=str,
        default=None,
        help="Capture a jax.profiler trace of one steady-state epoch, from "
        "its epoch_start to the end of its boundary (validation, save, "
        "bookkeeping: where the chip waits for the host), into this "
        "directory (view with TensorBoard's profile plugin / Perfetto; "
        "benchmark/tools/boundary_table.py prints the idle time by host span)",
    )
    # serving (serve/ subsystem: engine + micro-batcher + load generators)
    parser.add_argument(
        "--serve",
        action="store_true",
        default=False,
        help="Run the batched/sharded inference engine + load harness "
        "instead of training: restore a checkpoint (--serve-ckpt), "
        "compile one predict program per batch bucket, and drive it with "
        "the configured load generator, printing a latency/throughput "
        "report (serve/)",
    )
    parser.add_argument(
        "--serve-ckpt",
        type=str,
        default=None,
        help="Checkpoint to serve (a best_model_*.ckpt or last.ckpt). "
        "Default: the newest version dir's best checkpoint under "
        "--ckpt-path; if none exists the engine serves fresh-initialized "
        "weights (load-testing mode) with a warning",
    )
    parser.add_argument(
        "--serve-buckets",
        type=str,
        default="1,2,4,8,16,32",
        help="Comma-separated padded batch-size buckets. Ragged request "
        "batches round up to the nearest bucket, so jit compiles exactly "
        "one predict program per bucket and ragged traffic never "
        "recompiles; the largest bucket is the micro-batcher's "
        "max coalesced batch",
    )
    parser.add_argument(
        "--max-wait-ms",
        type=float,
        default=2.0,
        help="Bucketed-mode coalescing window: a batch is dispatched when "
        "it reaches the largest bucket or the oldest queued request has "
        "waited this long (continuous mode ignores it — the previous "
        "dispatch IS the window)",
    )
    parser.add_argument(
        "--serve-mode",
        type=str,
        default="continuous",
        choices=("continuous", "bucketed"),
        help="Batch admission policy: 'continuous' (production fast path "
        "— queued requests are admitted into the next dispatch at every "
        "step boundary, slot-filling the bucket ladder; kills the "
        "flush-timeout tail cliff under partial load) or 'bucketed' (the "
        "classic max-wait window, kept as the comparable baseline)",
    )
    parser.add_argument(
        "--serve-replicas",
        type=int,
        default=1,
        help="Engine replicas behind the router (serve/router.py): each "
        "owns its own AOT bucket programs and pulls from one shared "
        "SLO-class queue.  0 = size the fleet with the planner's "
        "ledger-fit cost model (parallel/planner.py) from the committed "
        "compile ledger under --ckpt-path and the offered --serve-rate",
    )
    parser.add_argument(
        "--serve-transport",
        type=str,
        default="thread",
        choices=("thread", "process"),
        help="Replica substrate: 'thread' (N engines in this process "
        "sharing one jax runtime — the fast in-test default) or "
        "'process' (serve/fleet/: each replica is a real OS process "
        "with its own jax runtime, device set, and exporter port, "
        "reached over the length-prefixed socket transport, supervised "
        "with restart budget + backoff; a worker that dies mid-dispatch "
        "gets its batch requeued, not failed)",
    )
    parser.add_argument(
        "--serve-scale-target",
        type=str,
        default="",
        help="Queueing-aware autoscaling targets (serve/fleet/"
        "autoscale.py): '[CLASS:]p99=MILLIS[,...]' — fit a G/G/m tail "
        "from the measured service/arrival sketches and re-size the "
        "fleet to the smallest replica count whose predicted p99 meets "
        "every target (scale-up immediate, scale-down hysteretic, both "
        "behind a cooldown, every decision a serve_scale event).  "
        "Empty = fixed fleet.  E.g. 'p99=400' or 'gold:p99=150'",
    )
    parser.add_argument(
        "--serve-trace-sample",
        type=float,
        default=0.0,
        help="Head-sample rate for request tracing (obs/reqtrace.py), in "
        "[0, 1].  Every request carries trace context either way; full "
        "span records are always kept for shed / expired / "
        "deadline-breached / requeued / errored requests (tail-based "
        "keep), plus a seeded fraction of healthy ones at this rate.  "
        "0 = tail-only (the near-free default); run_report --trace "
        "merges kept spans across the router's and every replica "
        "process's event files into the per-class critical-path "
        "decomposition",
    )
    parser.add_argument(
        "--serve-port-base",
        type=int,
        default=0,
        help="Process-transport request-port base: replica RID listens "
        "on base+RID (deterministic, so N same-host workers never "
        "collide).  0 = each worker binds an ephemeral port and reports "
        "it through its handshake file",
    )
    parser.add_argument(
        "--serve-max-replicas",
        type=int,
        default=8,
        help="Autoscaler fleet-size ceiling (and plan_serve's clamp)",
    )
    parser.add_argument(
        "--serve-classes",
        type=str,
        default="",
        help="Per-tenant SLO classes: comma-separated "
        "'NAME:priority=P:deadline_ms=D:target=F' entries (lower "
        "priority = more important; deadline_ms is the class default a "
        "per-request deadline overrides; target is the attainment "
        "fraction run_report --serve gates on).  Empty = one 'default' "
        "class.  E.g. 'gold:priority=0:deadline_ms=250:target=0.99,"
        "batch:priority=2'",
    )
    parser.add_argument(
        "--serve-warm-buckets",
        type=str,
        default="",
        help="Bucket subset to warm at startup (comma-separated; empty = "
        "the whole ladder) — the deployment shape 'warm my expected "
        "traffic'; a flash crowd landing on an unwarmed bucket trips the "
        "recompilation sentinel (and, under a rewarm_serve --policy "
        "rule, re-warms the fleet)",
    )
    parser.add_argument(
        "--serve-aot-cache",
        type=str,
        default="auto",
        help="Persisted AOT executable store (utils/compile_cache.py): "
        "serve bucket programs serialize under their CompileMonitor "
        "fingerprint so a cold replica deserializes its ladder in "
        "milliseconds instead of recompiling.  'auto' = <ckpt-path>/"
        "serve-aot, 'off' = disabled, anything else = explicit directory",
    )
    parser.add_argument(
        "--serve-shape",
        type=str,
        default="auto",
        choices=("auto", "closed", "open", "flash", "diurnal", "mixed"),
        help="Load shape: 'auto' (open loop when --serve-rate > 0, else "
        "closed), 'flash' (rate step x--serve-flash-mult for the middle "
        "third, per-phase latency in the report), 'diurnal' (sinusoidal "
        "ramp to 4x base), 'mixed' (one open loop per SLO class)",
    )
    parser.add_argument(
        "--serve-flash-mult",
        type=float,
        default=8.0,
        help="Flash-crowd rate multiplier for --serve-shape flash",
    )
    parser.add_argument(
        "--queue-limit",
        type=int,
        default=256,
        help="Load-shed bound: submissions beyond this queue depth are "
        "rejected with a typed QueueOverflow error (graceful degradation "
        "instead of unbounded latency)",
    )
    parser.add_argument(
        "--serve-rate",
        type=float,
        default=0.0,
        help="Open-loop load: Poisson arrival rate in requests/sec "
        "(0 = closed-loop at --serve-concurrency in-flight requests)",
    )
    parser.add_argument(
        "--serve-requests",
        type=int,
        default=512,
        help="Total requests the load generator offers",
    )
    parser.add_argument(
        "--serve-concurrency",
        type=int,
        default=8,
        help="Closed-loop load: number of in-flight requests",
    )
    parser.add_argument(
        "--deadline-ms",
        type=float,
        default=0.0,
        help="Per-request deadline; expired requests are failed with a "
        "typed DeadlineExceeded error before wasting compute (0 = none)",
    )
    # resilience (resilience/ subsystem: faults + preemption + supervisor +
    # crash-safe checkpoint I/O + elastic restore + goodput accounting)
    parser.add_argument(
        "--resilience",
        action="store_true",
        default=False,
        help="Preemption-aware mode: install the SIGTERM handler (drain "
        "the async checkpointer, force a final last.ckpt, exit with the "
        "distinct EXIT_PREEMPTED code the supervisor restarts on). "
        "Goodput accounting always runs; this flag adds the signal "
        "machinery (resilience/)",
    )
    parser.add_argument(
        "--supervise",
        action="store_true",
        default=False,
        help="Run the restart supervisor instead of training directly: "
        "relaunch this same command (with --auto-resume --resilience) "
        "until clean exit, restarting immediately on preemption and with "
        "exponential backoff on crashes, up to --max-restarts; aggregates "
        "goodput across attempts into GOODPUT.json. CLI-only",
    )
    parser.add_argument(
        "--fleet-hosts",
        type=int,
        default=0,
        metavar="N",
        help="Elastic fleet supervision (with --supervise): own N host "
        "processes per attempt instead of one command, re-rendering "
        "--world-size/--rank and a fresh --dist-url rendezvous from the "
        "surviving host pool at every attempt boundary. A host killed by "
        "a signal (or marked via <ckpt>/fleet/host-i.down) shrinks the "
        "fleet to the widest legal world size; host-i.up re-admits it and "
        "triggers a deliberate drain-checkpoint-and-re-expand. 0/1 = the "
        "single-command supervisor (unchanged)",
    )
    parser.add_argument(
        "--fleet-min-hosts",
        type=int,
        default=1,
        help="Refusal floor for the elastic pool: when no legal world "
        "size >= this survives (batch divisibility, tensor-parallel "
        "degree), the supervisor refuses with the actual numbers instead "
        "of launching a doomed attempt",
    )
    parser.add_argument(
        "--fleet-local-devices",
        type=int,
        default=0,
        help="Devices per fleet host, used to pick the widest legal world "
        "size AND (CPU emulation: tests) forced into each child via "
        "XLA_FLAGS. 0 = inherit the environment (real TPU hosts)",
    )
    parser.add_argument(
        "--fleet-grace-secs",
        type=float,
        default=15.0,
        help="Drain grace window: after SIGTERM-ing an attempt's "
        "surviving ranks (peer died / deliberate resize), ranks still "
        "alive past this many seconds are SIGKILLed — a host wedged in a "
        "collective whose peer vanished can never reach its drain poll",
    )
    parser.add_argument(
        "--fleet-poll-secs",
        type=float,
        default=1.0,
        help="Fleet watcher steady-state poll cadence (the event-file "
        "tail driving stall/alert evaluation). The poll tightens itself "
        "to ~100ms while any host is degraded (slow/stuck/dead), so "
        "escalations and recoveries land with sub-second latency without "
        "paying a fast poll on a healthy fleet",
    )
    parser.add_argument(
        "--fleet-probe",
        type=str,
        default="",
        metavar="SPEC",
        help="Scheduler re-admission probe, polled by the fleet "
        "supervisor for every LOST host: 'file:PATH' (slot schedulable "
        "when PATH exists; {host} substituted) or 'exec:CMD' (shell "
        "command, exit 0 = schedulable; {host} substituted, else the "
        "host index is appended). A schedulable answer writes the same "
        "host-i.up marker an operator would; probe infrastructure "
        "failures degrade to the manual marker path with one warning. "
        "Default '' = markers only",
    )
    parser.add_argument(
        "--max-restarts",
        type=int,
        default=3,
        help="Supervisor restart budget (crashes and preemptions both "
        "count toward it; preemptions skip the backoff)",
    )
    parser.add_argument(
        "--restart-backoff",
        type=float,
        default=1.0,
        help="Base seconds for the supervisor's exponential crash backoff "
        "(doubles per crash, capped at 60s)",
    )
    parser.add_argument(
        "--fault-plan",
        type=str,
        default=None,
        help="Deterministic fault-injection spec, ';'-separated events: "
        "preempt@epoch=K, ckpt_fail@epoch=K, torn_write@epoch=K, "
        "stall@epoch=K:secs=S, or kind@prob=P (seeded per-epoch "
        "Bernoulli). Fires at epoch boundaries; epoch=K events are "
        "naturally one-shot across supervised restarts (resume moves past "
        "K). See resilience/faults.py",
    )
    parser.add_argument(
        "--fault-seed",
        type=int,
        default=0,
        help="Seed for prob= fault-plan draws (deterministic per "
        "(seed, kind, epoch))",
    )
    # eager-parity debug rail (parity/ subsystem: record the first N real
    # steps, replay them through the same executable family bitwise, and
    # diff against the no-jit eager reference under a ulp tolerance)
    parser.add_argument(
        "--parity-check",
        type=int,
        default=0,
        help="Record the first N steps of the first trained epoch (one "
        "step per dispatch — bit-identical by the runners' chunking "
        "contract), then replay them through a fresh instance of the same "
        "scanned executable (bitwise replay gate) and through the eager "
        "no-jit reference rail (tolerance-gated). Emits one 'parity' "
        "event; render/gate it with tools/run_report.py --parity. "
        "Single-process debug rail; 0 disables",
    )
    parser.add_argument(
        "--parity-tol",
        type=str,
        default=f"ulp={1 << 26}",
        help="Reference-gate tolerance: 'bitwise' (exact — expected to "
        "fail for any real layout, XLA fusion re-associates float math) "
        "or 'ulp=K' (scale-aware: max |a-b| within K float32 ulps at the "
        "leaf's largest magnitude). Measured bands on the 8-device CPU "
        "mesh: conv-family dp-only fp32 ~2^6-2^8; attention trunks, "
        "tp/pp splits, and the fp16/int8 wire tiers all reassociate "
        "into ~2^23-2^25. The default covers every stock layout; "
        "TIGHTEN per run by capturing once with a loose K and reading "
        "max_ulp off the event (e.g. ulp=1024 for conv dp runs). The "
        "replay gate is always bitwise regardless",
    )
    parser.add_argument(
        "--parity-corrupt",
        type=str,
        default=None,
        help="Silicon-fault simulator for the parity rail, "
        "'STEP:BIT:LEAF-SUBSTRING': after capture step STEP, flip bit BIT "
        "of element 0 of the first state leaf matching the substring in "
        "the REAL carried state; the clean replay must localize the flip "
        "to exactly that (step, leaf)",
    )
    parser.add_argument(
        "--goodput-json",
        type=str,
        default=None,
        help="Also write the aggregated goodput report to this path at the "
        "end of the run (the supervisor always writes GOODPUT.json)",
    )
    # training health (health/ subsystem: compiled numerics guards + spike
    # detection + cross-replica desync detection + automatic rollback)
    parser.add_argument(
        "--health",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Training-health watchdog: per-step NaN/Inf guards already "
        "skip non-finite updates inside the compiled step; the watchdog "
        "additionally detects loss spikes (rolling median/MAD) and "
        "cross-replica desync (param fingerprints), and rolls back to the "
        "last good checkpoint on sustained badness. --no-health restores "
        "the bare abort-on-divergence behavior (guards stay on)",
    )
    parser.add_argument(
        "--health-window",
        type=int,
        default=64,
        help="Spike detector: rolling window of recent GOOD per-step "
        "losses the median/MAD baseline is computed over",
    )
    parser.add_argument(
        "--health-spike-mads",
        type=float,
        default=8.0,
        help="Spike detector: a step flags as a spike when its loss "
        "exceeds the rolling median by this many MADs",
    )
    parser.add_argument(
        "--health-bad-steps",
        type=int,
        default=3,
        help="Rollback trigger: K consecutive bad steps (skipped "
        "non-finite or spiked) in an epoch roll the run back to the last "
        "good checkpoint; fewer are absorbed (skips cost only the lost "
        "update — the compiled guard already kept the state clean)",
    )
    parser.add_argument(
        "--health-max-rollbacks",
        type=int,
        default=3,
        help="Rollback budget per attempt: a fault that deterministically "
        "re-fires on replay must abort loudly, not loop",
    )
    parser.add_argument(
        "--health-desync-every",
        type=int,
        default=1,
        help="Check cross-replica param fingerprints every N epochs "
        "(0 disables); any mismatch rolls back — replicas that silently "
        "drifted apart must never keep training",
    )
    parser.add_argument(
        "--health-quarantine",
        action="store_true",
        default=False,
        help="Corrupt-shard quarantine (host data mode): when a rollback "
        "replays an epoch, the bad step window's batch EXAMPLE indices "
        "are handed to the loader, which excludes them and deterministically "
        "substitutes clean examples — a persistently corrupt shard stops "
        "re-firing the same rollback. Off by default: quarantining changes "
        "the replayed trajectory, so it is an explicit operator decision",
    )
    parser.add_argument(
        "--health-json",
        type=str,
        default=None,
        help="Write the HEALTH.json summary (skip/spike/rollback/desync "
        "counts + events) to this path at the end of the run; per-event "
        "records always land in the run dir's health.jsonl",
    )
    # observability (obs/ subsystem: run-event bus + span tracing + flight
    # recorder; tools/run_report.py merges/validates the artifacts)
    parser.add_argument(
        "--obs",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Run-event bus + span tracing: append every run event "
        "(epochs, health verdicts, rollbacks, preemptions, writer gauges, "
        "goodput) to the version dir's events.jsonl under one versioned "
        "schema, and export the host-thread span timeline as a "
        "Chrome-trace/Perfetto trace.json. --no-obs writes neither file "
        "and keeps only the in-memory flight-recorder ring (which still "
        "dumps crash_dump.json on abort — forensics survive the opt-out)",
    )
    parser.add_argument(
        "--flight-recorder-size",
        type=int,
        default=256,
        help="Bounded in-memory ring of the last N run events, dumped to "
        "crash_dump.json on abort, watchdog budget exhaustion, or an "
        "unhandled exception — the post-mortem that no longer depends on "
        "scraping log files",
    )
    parser.add_argument(
        "--flight-ring",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Mirror the flight recorder into an mmap'd fixed-slot "
        "flight*.ring file next to the event files: the OS page cache "
        "keeps the slots, so the last N events survive SIGKILL/OOM — the "
        "deaths crash_dump.json can never catch.  The supervisor pulls "
        "every host's ring into one blackbox.json after each attempt "
        "(no-op under --no-obs, which writes no files)",
    )
    parser.add_argument(
        "--metrics-flush-steps",
        type=int,
        default=50,
        metavar="N",
        help="Per-step sampling budget: grad_norm/loss/step-phase samples "
        "are recorded into typed in-memory sketches EVERY step, and the "
        "bus sees one bounded 'metrics' event per N trained steps (plus "
        "one per epoch end).  Histogram sketches merge associatively "
        "across flushes/hosts/attempts, so run_report reconstructs "
        "p50/p95/p99 for any slice of the run from the event stream",
    )
    parser.add_argument(
        "--heartbeat-secs",
        type=float,
        default=10.0,
        metavar="S",
        help="Liveness cadence: each process emits a tiny 'heartbeat' "
        "event (position + metric-flush sequence) at most once per S "
        "seconds, checked at the chunk boundaries the trainer already "
        "touches.  The supervisor's fleet watcher classifies a host whose "
        "heartbeats go stale as slow (3 missed beats) vs dead (10) — and "
        "a host beating on schedule whose STEP stops advancing as stuck "
        "(livelock) — and emits a 'stall' event before the collective "
        "wedges.  0 disables heartbeats (and therefore stall detection)",
    )
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=0,
        metavar="PORT",
        help="OpenMetrics text-exposition endpoint: each process serves "
        "its live metric registry (cumulative counters/histograms), "
        "heartbeat age, and alert states at http://:PORT+process_index"
        "/metrics from a stdlib http.server thread.  0 (default) = off; "
        "scrape-less setups can render the same exposition offline with "
        "run_report --export-openmetrics",
    )
    parser.add_argument(
        "--alert",
        action="append",
        default=None,
        metavar="SPEC",
        help="Declarative alert rule, repeatable: METRIC:AGG{><}THRESHOLD"
        "[:for=N], e.g. 'serve/latency_s:p99>0.25:for=3' (p99 above 250ms "
        "for 3 consecutive flush windows), 'heartbeat:age>30' (any "
        "process silent 30s), or 'compile/recompiles_after_warmup:n>0' "
        "(the recompilation sentinel).  AGG: p50/p95/p99/mean/max/min/"
        "count (histograms), value (gauges), n (counters), age (heart"
        "beat).  for=N is the hysteresis: N consecutive breaching windows "
        "to fire, N clean ones to resolve.  Fleet aggregates — "
        "'sum(METRIC):AGG>THR' or max(...) — fold every process's latest "
        "window value into one fleet-wide number, evaluated by the "
        "supervisor only (the one consumer that sees every host's "
        "stream).  Per-process rules evaluate supervisor-side too "
        "(in-process for unsupervised runs); transitions emit "
        "firing/resolved 'alert' events that run_report --alerts turns "
        "into a timeline and a CI exit code",
    )
    parser.add_argument(
        "--policy",
        action="append",
        default=None,
        metavar="SPEC",
        help="Closed-loop autopilot rule, repeatable: 'ALERT -> ACTION"
        "[:cooldown=S]' binds a firing --alert rule (matched by its full "
        "spec or its metric name) to an action — drain_host (write the "
        "same <ckpt>/fleet/host-i.down marker an operator writes today), "
        "rewarm_serve (re-run warmup() on the recompiled bucket subset), "
        "rollback (the watchdog's verified-restore path), or "
        "abort_with_evidence (orderly abort with the blackbox ring + "
        "alert/policy timelines in crash_dump.json, and the supervisor "
        "stops relaunching).  Example: 'step/dispatch_s:p95>30:for=2 -> "
        "drain_host:cooldown=120'.  Every decision emits a 'policy' "
        "event; per-rule cooldowns (default 60s) and --policy-max-actions "
        "bound what a flapping alert can drive.  Evaluated wherever the "
        "alerts are: supervisor-side for supervised runs, in-process "
        "otherwise.  See ops/policy.py and run_report --policy",
    )
    parser.add_argument(
        "--policy-mode",
        type=str,
        default="dry-run",
        choices=["off", "dry-run", "act"],
        help="Autopilot mode: 'dry-run' (default) makes every decision — "
        "cooldowns and budget advance exactly as they would — and logs "
        "what it WOULD have done without running any action; 'act' runs "
        "them; 'off' disables the engine entirely.  The runbook is: "
        "watch a dry-run's policy timeline, then flip to act",
    )
    parser.add_argument(
        "--policy-max-actions",
        type=int,
        default=4,
        metavar="N",
        help="Global actions-per-attempt budget for the policy engine: "
        "at most N decisions act (or dry-run-log) per supervised "
        "attempt, so an alert storm cannot drain the whole fleet in one "
        "attempt.  The budget re-grants at every attempt start (and on "
        "a 15-minute clock in attempt-less sessions — serving must "
        "rate-limit re-warms, not lose them forever)",
    )
    parser.add_argument(
        "--control-boundary",
        type=str,
        default="chunk",
        choices=["chunk", "epoch"],
        help="Where supervisor/policy decisions APPLY: 'chunk' (default) "
        "lands rollback/abort/drain_host/replan requests as durable "
        "control-*.req files the trainer consumes at every chunk "
        "boundary — the same poll site as mid-epoch preemption, so "
        "time-to-mitigation is bounded by one chunk, not one epoch; "
        "'epoch' keeps the legacy policy-*.req channel applied at the "
        "next epoch boundary (the PR-12 behavior; tests/test_control.py "
        "runs both). Every application emits a 'control' event carrying "
        "decide->apply latency; see run_report --policy",
    )
    parser.add_argument(
        "--health-phase-baselines",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="Spike detection keeps a separate median/MAD baseline per LR "
        "plateau (keyed off the StepLR schedule) instead of one global "
        "window: the loss distribution shifts at every decay, and a "
        "post-decay epoch judged against pre-decay losses is a false "
        "positive waiting to happen",
    )
    parser.add_argument(
        "--legacy-test-stats",
        action="store_true",
        default=False,
        help="Reproduce the reference's test-set normalization quirk "
        "(ImageNet stats at test time, src/single/dataset.py:130-133; "
        "SURVEY.md §5 quirk 4) for comparison runs",
    )
    return parser


def load_config(
    backend: str = "single", argv: Sequence[str] | None = None
) -> argparse.Namespace:
    """Parse flags.  ``argv=None`` reads ``sys.argv`` like the reference."""
    parser = build_parser(backend)
    args = parser.parse_args(argv)
    args.backend = backend
    if args.limit_examples < 0:
        parser.error(f"--limit-examples must be >= 0, got {args.limit_examples}")
    if args.valid_examples < 0:
        parser.error(f"--valid-examples must be >= 0, got {args.valid_examples}")
    if args.max_restarts < 0:
        parser.error(f"--max-restarts must be >= 0, got {args.max_restarts}")
    if args.health_window < 4:
        parser.error(f"--health-window must be >= 4, got {args.health_window}")
    if args.health_bad_steps < 1:
        parser.error(
            f"--health-bad-steps must be >= 1, got {args.health_bad_steps}"
        )
    if args.health_max_rollbacks < 0:
        parser.error(
            f"--health-max-rollbacks must be >= 0, got {args.health_max_rollbacks}"
        )
    if args.health_desync_every < 0:
        parser.error(
            f"--health-desync-every must be >= 0, got {args.health_desync_every}"
        )
    if args.restart_backoff < 0:
        parser.error(f"--restart-backoff must be >= 0, got {args.restart_backoff}")
    if args.pipeline_parallel < 1:
        parser.error(
            f"--pipeline-parallel must be >= 1, got {args.pipeline_parallel}"
        )
    if args.pipeline_virtual_stages < 0:
        parser.error(
            f"--pipeline-virtual-stages must be >= 0, got "
            f"{args.pipeline_virtual_stages}"
        )
    if args.pipeline_virtual_stages > 1 and args.pipeline_schedule != "interleaved":
        parser.error(
            "--pipeline-virtual-stages > 1 needs --pipeline-schedule "
            "interleaved (gpipe/1f1b schedule one contiguous slice per stage)"
        )
    if args.pipeline_parallel > 1 and args.parallel_style != "tensor":
        parser.error(
            "--pipeline-parallel composes with --parallel-style tensor "
            "(the model axis keeps its tensor-parallel meaning; "
            "--parallel-style pipeline is the legacy single-axis spelling "
            "— use one or the other)"
        )
    if args.fleet_hosts < 0:
        parser.error(f"--fleet-hosts must be >= 0, got {args.fleet_hosts}")
    if args.fleet_hosts > 1 and not args.supervise:
        parser.error("--fleet-hosts needs --supervise (the elastic pool is "
                     "a supervisor mode)")
    if args.fleet_min_hosts < 1:
        parser.error(
            f"--fleet-min-hosts must be >= 1, got {args.fleet_min_hosts}"
        )
    if args.fleet_local_devices < 0:
        parser.error(
            f"--fleet-local-devices must be >= 0, got {args.fleet_local_devices}"
        )
    if args.fleet_grace_secs < 0:
        parser.error(
            f"--fleet-grace-secs must be >= 0, got {args.fleet_grace_secs}"
        )
    if args.fleet_poll_secs <= 0:
        parser.error(
            f"--fleet-poll-secs must be > 0, got {args.fleet_poll_secs}"
        )
    if args.fleet_hosts > 1 and args.world_size > 1:
        parser.error(
            "--fleet-hosts re-renders --world-size/--rank per attempt; "
            "do not pass --world-size with the elastic pool"
        )
    if args.fleet_probe:
        kind, _, arg = args.fleet_probe.partition(":")
        if kind not in ("exec", "file") or not arg:
            parser.error(
                f"--fleet-probe must be 'exec:CMD' or 'file:PATH', "
                f"got {args.fleet_probe!r}"
            )
        if args.fleet_hosts <= 1:
            parser.error(
                "--fleet-probe is the elastic pool's re-admission "
                "signal; it needs --fleet-hosts > 1"
            )
    if args.flight_recorder_size < 1:
        parser.error(
            f"--flight-recorder-size must be >= 1, got {args.flight_recorder_size}"
        )
    if args.metrics_flush_steps < 1:
        parser.error(
            f"--metrics-flush-steps must be >= 1, got {args.metrics_flush_steps}"
        )
    if args.device_chunk_steps < 0:
        parser.error(
            f"--device-chunk-steps must be >= 0, got {args.device_chunk_steps}"
        )
    # --device-prefetch: an int depth, or 'auto' (per-host HBM-derived)
    if isinstance(args.device_prefetch, str):
        if args.device_prefetch.strip().lower() == "auto":
            args.device_prefetch = "auto"
        else:
            try:
                args.device_prefetch = int(args.device_prefetch)
            except ValueError:
                parser.error(
                    f"--device-prefetch must be an integer >= 0 or 'auto', "
                    f"got {args.device_prefetch!r}"
                )
    if args.device_prefetch != "auto" and args.device_prefetch < 0:
        parser.error(
            f"--device-prefetch must be >= 0, got {args.device_prefetch}"
        )
    if args.heartbeat_secs < 0:
        parser.error(
            f"--heartbeat-secs must be >= 0, got {args.heartbeat_secs}"
        )
    if args.parity_check < 0:
        parser.error(
            f"--parity-check must be >= 0, got {args.parity_check}"
        )
    if args.parity_check or args.parity_corrupt:
        # malformed tolerance/corrupt specs die at the CLI, not after the
        # capture epoch already trained (same contract as --alert/--policy)
        from .parity import Tolerance, parse_corrupt

        try:
            Tolerance.parse(args.parity_tol)
        except ValueError as e:
            parser.error(str(e))
        if args.parity_corrupt:
            try:
                parse_corrupt(args.parity_corrupt)
            except ValueError as e:
                parser.error(str(e))
        if args.parity_corrupt and not args.parity_check:
            parser.error("--parity-corrupt requires --parity-check N")
    if not 0 <= args.metrics_port <= 65535:
        parser.error(
            f"--metrics-port must be in [0, 65535], got {args.metrics_port}"
        )
    alert_rules = []
    if args.alert:
        # a malformed alert rule must die at the CLI, not at the first
        # flush of a run that already burned its startup/compile time
        from .obs.alerts import AlertSpecError, parse_alert_specs

        try:
            alert_rules = parse_alert_specs(args.alert)
        except AlertSpecError as e:
            parser.error(str(e))
    if args.policy_max_actions < 1:
        parser.error(
            f"--policy-max-actions must be >= 1, got {args.policy_max_actions}"
        )
    if args.policy:
        # same contract as --alert/--fault-plan: a malformed policy rule
        # (or one whose trigger names no alert rule and thus can never
        # fire) dies at the CLI, not in a post-mortem
        from .ops.policy import (
            PolicySpecError,
            parse_policy_specs,
            validate_policy_rules,
        )

        try:
            validate_policy_rules(parse_policy_specs(args.policy), alert_rules)
        except PolicySpecError as e:
            parser.error(str(e))
    if args.fault_plan:
        # a malformed fault plan must die at the CLI, not at epoch 0 of a
        # run that already burned its startup/compile time
        from .resilience.faults import FaultPlan, FaultSpecError

        try:
            FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
        except FaultSpecError as e:
            parser.error(str(e))
    if args.precision is None:
        args.precision = "bf16" if args.amp else "fp32"
    try:
        buckets = tuple(
            sorted({int(t) for t in args.serve_buckets.split(",") if t.strip()})
        )
    except ValueError:
        buckets = ()
    if not buckets or buckets[0] < 1:
        parser.error(
            f"--serve-buckets must be positive integers, got "
            f"{args.serve_buckets!r}"
        )
    args.serve_buckets = buckets
    try:
        warm = tuple(
            sorted(
                {int(t) for t in args.serve_warm_buckets.split(",") if t.strip()}
            )
        )
    except ValueError:
        parser.error(
            f"--serve-warm-buckets must be integers, got "
            f"{args.serve_warm_buckets!r}"
        )
    bad = [b for b in warm if b not in buckets]
    if bad:
        parser.error(
            f"--serve-warm-buckets {bad} not in the --serve-buckets "
            f"ladder {list(buckets)}"
        )
    args.serve_warm_buckets = warm
    if args.serve_replicas < 0:
        parser.error(
            f"--serve-replicas must be >= 0 (0 = planner-sized), got "
            f"{args.serve_replicas}"
        )
    if args.serve_classes:
        # a malformed SLO class table dies at the CLI, like --alert and
        # --policy specs
        from .serve.batcher import SLOClassError, parse_slo_classes

        try:
            parse_slo_classes(args.serve_classes)
        except SLOClassError as e:
            parser.error(str(e))
    if args.serve_scale_target:
        # same contract: a malformed autoscale target dies at the CLI
        from .serve.fleet.autoscale import parse_scale_targets

        try:
            parse_scale_targets(args.serve_scale_target)
        except ValueError as e:
            parser.error(str(e))
    if not 0.0 <= args.serve_trace_sample <= 1.0:
        parser.error(
            f"--serve-trace-sample must be in [0, 1], got "
            f"{args.serve_trace_sample}"
        )
    if args.serve_port_base < 0 or args.serve_port_base > 65535:
        parser.error(
            f"--serve-port-base must be in [0, 65535], got "
            f"{args.serve_port_base}"
        )
    if args.serve_max_replicas < 1:
        parser.error(
            f"--serve-max-replicas must be >= 1, got "
            f"{args.serve_max_replicas}"
        )
    return args
