"""Benchmark: CIFAR-100 ResNet training throughput, images/sec/chip + MFU.

The reference never published throughput (SURVEY.md §6) — only accuracy
tables on 2× RTX 2080 Ti.  The driver's north star asks for images/sec/chip,
so ``vs_baseline`` is measured, not assumed: the baseline leg replicates the
reference's *loop architecture* on the same hardware — one dispatch per step,
a host→device copy of every batch, host-side shuffling, and a per-step
``loss.item()`` device sync (``src/single/trainer.py:126-153``) — while the
native legs are this framework's TPU path: device-resident data, in-jit
augmentation, one ``lax.scan`` dispatch per epoch.

Configs (BASELINE.json "configs"): rn18/bs256 bf16 (headline), rn18/bs256
fp32, rn50/bs512 bf16, the ImageNet-scale leg rn50@224px bf16 through the
7×7/2 + maxpool stem (synthetic data — the dataset itself is unobtainable
offline), and the transformer leg vit_tiny/bs256 bf16.  Each native leg
reports MFU = achieved training FLOP/s ÷ chip peak, with model FLOPs
counted analytically from the architecture (MACs × 2, backward ≈ 2×
forward).  A long-sequence flash-attention leg reports the Pallas kernel's
TF/s against the score-materializing jnp reference implementation.

Output: ONE JSON line on stdout, budgeted to ≤1.5 KB so it always fits the
driver's bounded tail capture (r4's full-detail line overflowed it and the
round's headline was recorded unparsed) —
``{"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
"detail": {ips/mfu/flash one number per leg}}``.  The complete per-leg
record is written to ``BENCH_DETAIL.json`` and mirrored to stderr.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_comparison_tpu import models, parallel
from distributed_training_comparison_tpu.data import synthetic_dataset
from distributed_training_comparison_tpu.train import (
    configure_optimizers,
    create_train_state,
    make_epoch_runner,
    make_train_step,
)


class HP:
    lr = 0.1
    weight_decay = 1e-4
    lr_decay_step_size = 25
    lr_decay_gamma = 0.1


# ----------------------------------------------------------- analytic FLOPs


def forward_flops_per_image(
    name: str,
    num_classes: int = 100,
    image_size: int = 32,
    stem: str = "cifar",
) -> float:
    """Analytic forward FLOPs/image for the ResNet zoo: conv MACs × 2 on the
    actual feature-map sizes, + the linear head.  BN/ReLU/pool omitted
    (<1% of conv FLOPs).  Architecture (block kind, depths, widths,
    strides) is read from the zoo model itself so this can never silently
    diverge from models/resnet.py."""
    from distributed_training_comparison_tpu.models.resnet import BasicBlock, ResNet

    m = models.get_model(name, num_classes=num_classes)
    kind = "basic" if m.block is BasicBlock else "bottleneck"
    depths = m.num_blocks
    widths, strides = ResNet.STAGE_WIDTHS, ResNet.STAGE_STRIDES
    exp = 1 if kind == "basic" else 4
    if stem == "imagenet":
        hw = image_size // 2  # 7×7 stride-2 conv
        macs = 7 * 7 * 3 * 64 * hw * hw
        hw //= 2  # 3×3 stride-2 maxpool
    else:
        hw = image_size
        macs = 3 * 3 * 3 * 64 * hw * hw  # 3×3 stride-1 CIFAR stem
    cin = 64
    for planes, stride, blocks in zip(widths, strides, depths):
        for i in range(blocks):
            s = stride if i == 0 else 1
            hw_out = hw // s
            if kind == "basic":
                macs += 3 * 3 * cin * planes * hw_out * hw_out
                macs += 3 * 3 * planes * planes * hw_out * hw_out
            else:
                macs += cin * planes * hw * hw  # 1×1 reduce (pre-stride)
                macs += 3 * 3 * planes * planes * hw_out * hw_out
                macs += planes * (planes * exp) * hw_out * hw_out
            if s != 1 or cin != planes * exp:
                macs += cin * planes * exp * hw_out * hw_out
            cin = planes * exp
            hw = hw_out
    macs += cin * num_classes
    return 2.0 * macs


def vit_forward_flops_per_image(model, image_size: int = 32) -> float:
    """Analytic forward FLOPs/image for a built zoo ViT, read off the model
    config: per block 12·d² MACs/token (qkv + proj + 4× MLP) plus the two
    attention matmuls (2·S·d MACs/token), plus patch embed and head."""
    m = model
    s = (image_size // m.patch) ** 2
    d = m.dim
    macs_per_token = m.depth * (12 * d * d + 2 * s * d)
    macs = s * (macs_per_token + m.patch * m.patch * 3 * d)  # + patch embed
    macs += d * m.num_classes
    return 2.0 * macs


def train_flops_per_image(
    name: str, image_size: int = 32, stem: str = "cifar", model_kw: dict | None = None
) -> float:
    """fwd + bwd ≈ 3× fwd (standard estimate: grad-wrt-input + grad-wrt-
    weights each cost ≈ one forward)."""
    if name.startswith("vit"):
        kw = {
            k: v
            for k, v in (model_kw or {}).items()
            if k in ("patch", "image_size")
        }
        return 3.0 * vit_forward_flops_per_image(
            models.get_model(name, **kw), image_size
        )
    return 3.0 * forward_flops_per_image(name, image_size=image_size, stem=stem)


# per-chip peak dense-matmul FLOP/s (bf16), by jax device_kind — ONE table,
# owned by obs/compilation.py (run_report --compute keys its measured-MFU
# denominator off the same numbers, so bench MFU and event-stream MFU can
# never disagree about what "peak" means)
from distributed_training_comparison_tpu.obs.compilation import (  # noqa: E402
    PEAK_FLOPS_BY_DEVICE_KIND as _PEAK_FLOPS,
    peak_flops_for as _peak_flops_for,
)


def chip_peak_flops() -> float | None:
    return _peak_flops_for(jax.devices()[0].device_kind)


# ----------------------------------------------------------------- harness


def _setup(
    mesh, model_name: str, precision: str, stem: str = "cifar",
    image_size: int = 32, model_kw: dict | None = None,
):
    model = models.get_model(
        model_name,
        dtype=jnp.bfloat16 if precision == "bf16" else jnp.float32,
        stem=stem,
        **(model_kw or {}),
    )
    tx, _ = configure_optimizers(HP, steps_per_epoch=100)
    state = create_train_state(
        model, jax.random.key(0), tx, input_shape=(1, image_size, image_size, 3)
    )
    return jax.device_put(state, parallel.replicated_sharding(mesh))


def bench_native(
    mesh, images, labels, model_name: str, precision: str, batch_size: int,
    epochs: int, stem: str = "cifar", model_kw: dict | None = None,
) -> float:
    """Native leg: scanned epoch over the HBM-resident split."""
    state = _setup(
        mesh, model_name, precision, stem, images.shape[1], model_kw
    )
    repl = parallel.replicated_sharding(mesh)
    d_images = jax.device_put(images, repl)
    d_labels = jax.device_put(labels, repl)
    runner = make_epoch_runner(mesh, batch_size, precision=precision)
    key = jax.random.key(1)
    steps = len(images) // batch_size

    # warmup epoch: compile + first execution
    state, stacked = runner(state, d_images, d_labels, key, jnp.asarray(0))
    float(stacked["loss"][-1])  # full sync

    t0 = time.perf_counter()
    for e in range(1, epochs + 1):
        state, stacked = runner(state, d_images, d_labels, key, jnp.asarray(e))
    float(stacked["loss"][-1])  # sync once at the end
    dt = time.perf_counter() - t0
    return epochs * steps * batch_size / dt


def bench_flash_attention(
    seqs: tuple = (2048, 4096, 8192, 16384, 32768), ref_seq: int = 4096
) -> dict:
    """Pallas flash-attention kernel: forward TF/s and fwd+bwd TF/s at each
    sequence length, causal and not (H=8, D=128, bf16; batch scaled to hold
    16384 total tokens, floored at 1 — so S=16384 runs batch 1 [the
    streamed-KV regime, making the README's long-S claims reproducible from
    this committed harness, VERDICT r4 item 2] and S=32768 runs batch 1 at
    DOUBLE the other legs' token budget; TF/s normalizes by FLOPs, so legs
    stay comparable even though wall-time per call does not).  The jnp-reference
    comparison runs at ``ref_seq`` only (it materializes the S×S scores in
    HBM, so it is both slow and memory-bound).  Kernel calls chain inside
    one ``lax.scan`` dispatch so dispatch latency amortizes away
    (the same one-dispatch trick the train path uses).

    FLOP accounting: forward = 4·b·h·S²·D (two matmuls, MACs×2); backward
    adds 6·b·h·S²·D (dq, dk, dv — three matmuls — plus the dp recompute
    counts the fwd's two against its one); causal halves everything."""
    from distributed_training_comparison_tpu.ops import (
        flash_attention,
        mha_reference,
    )

    h, d = 8, 128

    def qkv(seq):
        b = max(1, 16384 // seq)
        kq, kk, kv = jax.random.split(jax.random.key(0), 3)
        return (
            jax.random.normal(kq, (b, h, seq, d), jnp.bfloat16),
            jax.random.normal(kk, (b, h, seq, d), jnp.bfloat16),
            jax.random.normal(kv, (b, h, seq, d), jnp.bfloat16),
        )

    def timed_fwd(attn, q, k, v, m):
        @jax.jit
        def chain(q, k, v):
            def body(c, _):
                return attn(c, k, v), ()

            o, _ = jax.lax.scan(body, q, None, length=m)
            return o.astype(jnp.float32).sum()

        float(chain(q, k, v))  # compile + warm
        t0 = time.perf_counter()
        float(chain(q, k, v))
        return (time.perf_counter() - t0) / m

    def timed_fwd_bwd(attn, q, k, v, m):
        def loss(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        @jax.jit
        def chain(q, k, v):
            def body(c, _):
                g = jax.grad(loss, argnums=(0, 1, 2))(c, k, v)
                return c + 1e-6 * g[0], ()

            o, _ = jax.lax.scan(body, q, None, length=m)
            return o.astype(jnp.float32).sum()

        float(chain(q, k, v))
        t0 = time.perf_counter()
        float(chain(q, k, v))
        return (time.perf_counter() - t0) / m

    out = {"head_dim": d, "heads": h, "configs": {}}
    for seq in seqs:
        q, k, v = qkv(seq)
        b = q.shape[0]
        fwd_flops = 4.0 * b * h * seq * seq * d
        for causal in (False, True):
            key = f"s{seq}" + ("_causal" if causal else "")
            cfac = 0.5 if causal else 1.0
            try:
                t_f = _attempt(lambda: timed_fwd(
                    lambda q, k, v, c=causal: flash_attention(q, k, v, causal=c),
                    q, k, v, 150,
                ))
                t_fb = _attempt(lambda: timed_fwd_bwd(
                    lambda q, k, v, c=causal: flash_attention(q, k, v, causal=c),
                    q, k, v, 30,
                ))
                out["configs"][key] = {
                    "fwd_tflops": round(cfac * fwd_flops / t_f / 1e12, 1),
                    "fwd_bwd_tflops": round(cfac * 2.5 * fwd_flops / t_fb / 1e12, 1),
                }
            except Exception as e:  # pragma: no cover - evidence over abort
                out["configs"][key] = {"error": f"{type(e).__name__}: {e}"[:300]}
    try:
        q, k, v = qkv(ref_seq)
        b = q.shape[0]
        t_ref = timed_fwd(lambda q, k, v: mha_reference(q, k, v), q, k, v, 20)
        ref_tflops = 4.0 * b * h * ref_seq * ref_seq * d / t_ref / 1e12
        out["reference_impl_tflops"] = round(ref_tflops, 1)
        flash_ref = out["configs"].get(f"s{ref_seq}", {}).get("fwd_tflops")
        if flash_ref:
            out["speedup"] = round(flash_ref / ref_tflops, 1)
    except Exception as e:  # pragma: no cover
        out["reference_impl_error"] = f"{type(e).__name__}: {e}"[:300]
    return out


def bench_reference_style(mesh, images, labels, batch_size: int, steps: int) -> float:
    """Baseline leg: the reference's loop shape — python per-step loop,
    host-side shuffle + aug dispatch, H2D copy per batch, fp32, and a
    device→host loss fetch every step."""
    state = _setup(mesh, "resnet18", "fp32")
    step_fn = make_train_step(mesh, precision="fp32", augment=True)
    shard = parallel.batch_sharding(mesh)
    n = len(images)
    rng = np.random.default_rng(0)

    def one_step(i, state):
        idx = rng.integers(0, n, size=batch_size)
        bx = jax.device_put(images[idx], shard)  # H2D every step
        by = jax.device_put(labels[idx], shard)
        state, metrics = step_fn(state, bx, by, jax.random.key(i))
        float(metrics["loss"])  # per-step sync, like loss.item()
        return state

    state = one_step(0, state)  # compile
    t0 = time.perf_counter()
    for i in range(1, steps + 1):
        state = one_step(i, state)
    dt = time.perf_counter() - t0
    return steps * batch_size / dt


def run_legs(mesh, configs, n_chips, peak):
    """Run every training-throughput leg, failure-isolated: one leg's
    compile/OOM failure records ``{"error": ...}`` for that leg and must
    not zero the round's evidence (round 3 lost every number to a single
    leg — VERDICT r3 item 2).  Returns (per_config, dataset cache) — the
    caller picks the baseline leg's data out of the cache by the headline
    config's (n, image_size), so baseline and headline always share a
    workload even when an early leg errors out."""
    per_config = {}
    data_cache = {}  # identical (n, image_size) datasets generated once
    for cfg_key, model_name, precision, batch, image_size, stem, n, epochs, model_kw in configs:
        try:
            if (n, image_size) not in data_cache:
                data_cache[n, image_size] = synthetic_dataset(
                    n, num_classes=100, image_shape=(image_size, image_size, 3),
                    seed=0,
                )
            images, labels = data_cache[n, image_size]
            ips = bench_native(
                mesh, images, labels, model_name, precision, batch,
                epochs, stem, model_kw,
            )
            ips_chip = ips / n_chips
            flops = train_flops_per_image(model_name, image_size, stem, model_kw)
            # MFU only for bf16 legs: _PEAK_FLOPS is the bf16 dense-matmul
            # peak; fp32 peak differs per TPU generation, so a bf16-peak
            # ratio would not be a real utilization figure for the fp32
            # config
            mfu = (
                round(ips_chip * flops / peak, 4)
                if peak and precision == "bf16"
                else None
            )
            per_config[cfg_key] = {
                "images_per_sec_per_chip": round(ips_chip, 1),
                "train_flops_per_image": round(flops / 1e9, 3),  # GFLOPs
                "achieved_tflops": round(ips_chip * flops / 1e12, 2),
                "mfu": mfu,
            }
            if model_name.startswith("vit"):
                m = models.get_model(
                    model_name,
                    **{k: v for k, v in model_kw.items()
                       if k in ("patch", "image_size")},
                )
                tokens = (image_size // m.patch) ** 2
                per_config[cfg_key]["tokens_per_sec_per_chip"] = round(
                    ips_chip * tokens
                )
        except Exception as e:
            per_config[cfg_key] = {"error": f"{type(e).__name__}: {e}"[:500]}
        emit_progress(cfg_key, per_config[cfg_key])
    return per_config, data_cache


def main() -> None:
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    import sys

    enable_persistent_compilation_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not _explicit_cpu():
        # a measurement path that finds no chip fails; it never sizes down
        # to whatever backend answered
        sys.exit(
            f"bench.py: refused — no TPU found (platform {platform!r}); the "
            "CPU rehearsal sizing runs only under an explicit "
            "JAX_PLATFORMS=cpu"
        )
    mesh = parallel.make_mesh(backend="tpu")
    n_chips = mesh.shape["data"] * mesh.shape["model"] * mesh.shape.get("pipe", 1)
    peak = chip_peak_flops()

    # (key, model, precision, batch, image_size, stem, n_examples, epochs,
    #  model_kw) — model_kw reaches the zoo constructor (norm_dtype=None is
    # --bn-dtype compute, accuracy-validated in README; scan_unroll=-1 is
    # the trainer's own TPU default; patch overrides the ViT patch size)
    if platform == "cpu":  # rehearsal sizing (explicit JAX_PLATFORMS=cpu)
        ref_steps = 2
        configs = [
            ("resnet18_bf16_bs64", "resnet18", "bf16", 64, 32, "cifar", 256, 1, {}),
        ]
    else:
        ref_steps = 60
        configs = [
            # headline: the fastest accuracy-validated config — compute-dtype
            # BN statistics (--bn-dtype compute; measured accuracy-equal to
            # fp32 stats in the README's 50-epoch x3-seed study) is worth
            # +5.6% on the memory-bound CIFAR stem
            ("resnet18_bf16_bs256_bnc", "resnet18", "bf16", 256, 32, "cifar", 45_056, 3, {"norm_dtype": None}),
            # reference-parity BN semantics (fp32 stat reduction, like the
            # reference's AMP): the r1-r3 headline, kept for continuity
            ("resnet18_bf16_bs256", "resnet18", "bf16", 256, 32, "cifar", 45_056, 3, {}),
            ("resnet18_fp32_bs256", "resnet18", "fp32", 256, 32, "cifar", 45_056, 3, {}),
            # BASELINE.json config 4 continuity leg (bs512 global = 64/chip
            # on the spec's v3-8; here the whole 512 is one chip's load)
            ("resnet50_bf16_bs512", "resnet50", "bf16", 512, 32, "cifar", 45_056, 3, {}),
            # per-chip-realistic rn50 leg at the measured best config:
            # bs128 + compute-dtype BN stats (accuracy-validated)
            ("resnet50_bf16_bs128_bnc", "resnet50", "bf16", 128, 32, "cifar", 45_056, 3, {"norm_dtype": None}),
            # ImageNet-scale PROXY for BASELINE.json config 5 (which
            # specifies ImageNet-1k bs=1024 on v3-32): synthetic 224×224
            # inputs through the 7×7/2 + maxpool stem, 100-class head,
            # batch sized for one chip
            ("resnet50_bf16_bs128_224px", "resnet50", "bf16", 128, 224, "imagenet", 4_096, 2, {}),
            ("resnet50_bf16_bs128_224px_bnc", "resnet50", "bf16", 128, 224, "imagenet", 4_096, 2, {"norm_dtype": None}),
            # transformer family (beyond parity); unrolled trunk = the
            # trainer's TPU default path
            ("vit_tiny_bf16_bs256", "vit_tiny", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1}),
            # 256-token leg (patch 2): the long-sequence regime on CIFAR
            # inputs — served by the fused Pallas block kernel
            # (ops/vit_block.py; models/vit.py gates it on for
            # 128 <= S <= 512 on TPU, measured +28% on this leg)
            ("vit_tiny_p2_bf16_bs256", "vit_tiny", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1, "patch": 2}),
            # Switch-MoE legs, all three dispatch impls (README's MoE
            # cost-model numbers must be reproducible from this committed
            # harness — VERDICT r4 item 2).  The unmarked leg resolves
            # auto → the Pallas grouped-matmul kernel (ops/moe_gmm.py) on
            # TPU.  MFU counts dense-equivalent (one expert per token)
            # FLOPs, so capacity padding / router / dispatch all show up
            # as honest overhead
            ("vit_moe_bf16_bs256", "vit_moe", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1}),
            ("vit_moe_gather_bf16_bs256", "vit_moe", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1, "moe_dispatch": "gather"}),
            ("vit_moe_onehot_bf16_bs256", "vit_moe", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1, "moe_dispatch": "onehot"}),
            # the MoE trunk with num_experts=0: the depth-8/dim-192 dense
            # twin the cost model compares against
            ("vit_moe_dense_twin_bf16_bs256", "vit_moe", "bf16", 256, 32, "cifar", 45_056, 3, {"scan_unroll": -1, "num_experts": 0}),
            # long-context leg at the kernel's design point: 4096 tokens,
            # head dim 128 — the Pallas kernel carries the model's
            # attention in-training here
            ("vit_long_bf16_bs8_256px", "vit_long", "bf16", 8, 256, "cifar", 512, 2, {"scan_unroll": -1, "image_size": 256}),
        ]

    per_config, data_cache = run_legs(mesh, configs, n_chips, peak)
    ok = {k: v for k, v in per_config.items() if "error" not in v}
    headline_key = next(iter(ok), None)
    headline = ok[headline_key]["images_per_sec_per_chip"] if headline_key else None
    ref_style = None
    failed = [k for k, v in per_config.items() if "error" in v]
    if headline_key is not None:
        # the baseline leg replays exactly the headline config's workload —
        # looked up by headline_key, not position, so if the nominal
        # headline leg errors out the baseline follows whichever leg
        # actually headlines (ADVICE r4)
        hcfg = next(c for c in configs if c[0] == headline_key)
        try:
            h_images, h_labels = data_cache[hcfg[6], hcfg[4]]
            ref_style = bench_reference_style(
                mesh, h_images, h_labels, hcfg[3], ref_steps
            )
        except Exception as e:
            failed.append("reference_style")
            emit_progress(
                "reference_style", {"error": f"{type(e).__name__}: {e}"[:500]}
            )
    try:
        flash = (
            bench_flash_attention()
            if platform != "cpu" and n_chips == 1
            else None
        )
    except Exception as e:
        failed.append("flash_attention")
        flash = {"error": f"{type(e).__name__}: {e}"[:500]}

    record = {
        "metric": "cifar100_resnet18_train_throughput",
        "value": headline,
        "unit": "images/sec/chip",
        "vs_baseline": (
            round(headline * n_chips / ref_style, 3)
            if headline and ref_style
            else None
        ),
        "detail": {
            "platform": platform,
            "device_kind": jax.devices()[0].device_kind,
            "chips": n_chips,
            "chip_peak_bf16_tflops": round(peak / 1e12, 1) if peak else None,
            "headline_key": headline_key,
            "configs": per_config,
            "flash_attention": flash,
            "reference_style_images_per_sec": (
                round(ref_style, 1) if ref_style else None
            ),
            "baseline_definition": "same chip, reference loop shape: "
            "per-step dispatch + H2D copy + per-step host sync, fp32",
        },
    }
    # The full record goes to a file + stderr; stdout gets ONE budgeted
    # line.  The driver captures a bounded tail of stdout and parses the
    # final JSON line — r4's line outgrew that window and the round's
    # headline was recorded as ``parsed: null`` (VERDICT r4 item 1).
    with open("BENCH_DETAIL.json", "w") as f:
        json.dump(record, f, indent=1)
    emit_progress("full_record", record)
    print(compact_line(record))
    if failed:
        # the record above keeps every leg that did run; the exit code says
        # that not all of them did
        sys.exit(f"bench.py: {len(failed)} leg(s) failed: {', '.join(failed)}")


def compact_line(record: dict, budget: int = 1500) -> str:
    """Compress the bench record to one stdout JSON line of at most
    ``budget`` bytes: headline fields plus one number per training leg
    (images/sec/chip), per-leg MFU, and one number per flash config
    (fwd+bwd TF/s).  If the line still overflows — more legs than the
    budget can carry — the most verbose sections are dropped in order,
    never the headline fields.  The full record lives in
    ``BENCH_DETAIL.json``."""
    d = record["detail"]
    flash = d.get("flash_attention") or {}
    compact = {
        "metric": record["metric"],
        "value": record["value"],
        "unit": record["unit"],
        "vs_baseline": record["vs_baseline"],
        "detail": {
            "platform": d["platform"],
            "device_kind": d["device_kind"],
            "chips": d["chips"],
            "headline_key": d["headline_key"],
            "ips": {
                k: v.get("images_per_sec_per_chip", "err")
                for k, v in d["configs"].items()
            },
            "mfu": {
                k: v["mfu"]
                for k, v in d["configs"].items()
                if v.get("mfu") is not None
            },
            "flash_fwd_bwd_tflops": {
                k: v.get("fwd_bwd_tflops", "err")
                for k, v in (flash.get("configs") or {}).items()
            },
            "reference_style_images_per_sec": d["reference_style_images_per_sec"],
            "full_record": "BENCH_DETAIL.json",
        },
    }
    for drop in ("mfu", "flash_fwd_bwd_tflops", "ips"):
        line = json.dumps(compact)
        if len(line) <= budget:
            return line
        compact["detail"].pop(drop, None)
    return json.dumps(compact)


def emit_progress(key: str, result: dict) -> None:
    """Per-leg progress to stderr: a hard crash mid-run still leaves the
    completed legs' numbers on record (stdout stays reserved for the one
    final JSON line the driver parses)."""
    import sys

    print(f"[bench] {key}: {json.dumps(result)}", file=sys.stderr, flush=True)


def _explicit_cpu() -> bool:
    """True where the environment asks for the CPU backend by name."""
    import os

    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _require_explicit_cpu(mode: str) -> str:
    """Gate for every mode that starts child processes which need a device.

    A chip belongs to one process at a time: a parent that has touched JAX
    holds it, and a child that needs it then fails or hangs.  These modes'
    parents do touch JAX, and what they capture are counts and verdicts
    taken on (virtual) CPU devices — so they run only where the environment
    explicitly says ``JAX_PLATFORMS=cpu``, and anywhere else they exit
    non-zero with the reason before anything is initialized or spawned: no
    hang, and no CPU capture handed back from a chip machine.  Returns the
    platform (``"cpu"``)."""
    if not _explicit_cpu():
        raise SystemExit(
            f"bench.py {mode}: refused — this mode's parent holds the device "
            "while it starts children that need one, and its capture is a "
            "CPU capture; set JAX_PLATFORMS=cpu explicitly to take it"
        )
    return "cpu"


def bench_serve(out_path: str = "BENCH_SERVE.json") -> dict:
    """The serving leg, v2: the production fast path's scoreboard.

    Four legs, one committed JSON capture (``BENCH_SERVE.json``) the
    README's tables transcribe:

    1. **continuous vs bucketed** — the same warmed engine behind the
       two admission policies under the PARTIAL-LOAD shape (a LIGHT
       closed loop at concurrency 1 — the worker is idle as each
       request arrives, so the bucketed window's cost is structural,
       not scheduling noise — plus an open-loop Poisson leg at ~60% of
       measured capacity): the bucketed window vs step-boundary
       admission.  Headline: continuous throughput ÷ bucketed at
       matched-or-better p99.
    2. **cold start** — two REAL fresh processes against one persisted
       AOT store (``--serve-cold-child``): the first compiles and
       stores, the second deserializes by fingerprint.  The capture
       asserts the restarted replica's stream carries ZERO compile
       events that aren't ``cache: "persisted"`` and records the
       measured compile-seconds drop.
    3. **router scale-out** — 1 vs 2 replicas behind the shared
       SLO-class queue at closed-loop saturation (informational on CPU:
       replicas share the cores, so parity is expected and noted; the
       leg pins the routing machinery's overhead, not the speedup).
    4. **SLO classes** — mixed tenancy (gold with deadline+target,
       bulk) through the router; ``run_report --serve``'s per-class
       attainment gate runs as the leg's self-check.

    Weights are fresh-initialized (latency/throughput do not depend on
    their values).  Sized down on CPU so the capture is reproducible on
    the CI host.
    """
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.serve import (
        MicroBatcher,
        ServeEngine,
        ServeRouter,
        closed_loop,
        mixed_tenants,
        open_loop,
        parse_slo_classes,
        request_pool,
    )
    from distributed_training_comparison_tpu.utils import PersistedServeCache

    platform = _require_explicit_cpu("--serve")
    repo = os.path.dirname(os.path.abspath(__file__))
    # closed_conc=1 for the headline legs ON PURPOSE: the bucketed
    # window's cost is structural only when the worker is IDLE as a
    # request arrives (it then holds the lone request the full window
    # hoping a bucket fills) — at higher concurrency the window hides
    # under the previous dispatch's compute and the comparison decays
    # into run-to-run noise.  Concurrency-N behavior (slot-fill
    # coalescing) is pinned by the open-loop and router legs.
    # CPU sizing (the only platform this mode runs on: _require_explicit_cpu)
    model_name, image_size = "resnet18", 32
    buckets = (1, 4, 8, 16)
    closed_requests, closed_conc = 64, 1
    open_requests = 96
    router_requests, router_conc = 96, 8
    bucketed_wait_ms = 25.0

    # the capture's own event stream: bucket compiles land as `compile`
    # events, the router emits `serve_route`/`replica`, and the committed
    # record self-validates with run_report --check --require-kind
    # compile --require-kind serve_route — a silently-degraded hook
    # can't produce a trusted capture
    from distributed_training_comparison_tpu import obs

    serve_events_root = tempfile.mkdtemp(prefix="serve-bench-")
    aot_dir = os.path.join(serve_events_root, "serve-aot")
    # jax's persistent HLO cache is OFF for this capture (not moved: the
    # cache directory is placed from outside — utils/compile_cache.py): the
    # warmup must pay REAL compiles — an executable materialized from a
    # warm HLO cache serializes into an AOT blob whose fusion symbols are
    # missing on this jaxlib (the store-time round-trip verify refuses
    # it), so a cache-warm machine would otherwise commit a scoreboard
    # with zero persisted warm-starts.  It also makes warmup_compile_s
    # reproducible wherever the capture runs.
    jax.config.update("jax_enable_compilation_cache", False)
    bus = obs.configure(run_id=obs.new_run_id())
    bus.bind_dir(serve_events_root)
    registry = obs.MetricRegistry()
    monitor = obs.CompileMonitor(bus=bus, registry=registry)
    aot_cache = PersistedServeCache(aot_dir)

    legs: dict = {}

    def leg(key, fn):
        try:
            legs[key] = fn()
        except Exception as e:  # evidence over abort, like run_legs
            legs[key] = {"error": f"{type(e).__name__}: {e}"[:300]}
        emit_progress(key, legs[key])
        return legs[key]

    # ---- leg 1: continuous vs bucketed on ONE warmed engine ----------
    engine = ServeEngine(
        model_name=model_name,
        buckets=buckets,
        precision="bf16",
        image_size=image_size,
        monitor=monitor,
        aot_cache=aot_cache,
    )
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    images = request_pool(
        max(256, engine.max_bucket), image_size=image_size, seed=0
    )

    def engine_delta(before, after):
        """Per-LEG engine counters (the shared engine accumulates across
        legs; a leg's record must carry only its own traffic) — a
        mid-leg recompile poisoning one side of the continuous-vs-
        bucketed comparison must be diagnosable from the committed
        record."""
        return {
            "compiles": after["compiles"] - before["compiles"],
            "cache_hits": after["cache_hits"] - before["cache_hits"],
            "persisted_hits": (
                after["persisted_hits"] - before["persisted_hits"]
            ),
            "bucket_counts": {
                b: after["bucket_counts"][b] - before["bucket_counts"][b]
                for b in after["bucket_counts"]
            },
        }

    def closed_leg(mode):
        def run():
            before = engine.stats()
            with MicroBatcher(
                engine, max_wait_ms=bucketed_wait_ms, queue_limit=1024,
                mode=mode,
            ) as b:
                rep = closed_loop(
                    b, images, num_requests=closed_requests,
                    concurrency=closed_conc,
                )
            rep["mode_admission"] = mode
            rep["engine"] = engine_delta(before, engine.stats())
            return rep
        return run

    bucketed = leg("partial_closed_bucketed", closed_leg("bucketed"))
    continuous = leg("partial_closed_continuous", closed_leg("continuous"))

    # the open-loop partial shape at ~60% of measured continuous capacity
    open_rate = None
    if "error" not in continuous:
        open_rate = max(1.0, 0.6 * continuous["throughput_rps"])

        def open_leg(mode):
            def run():
                before = engine.stats()
                with MicroBatcher(
                    engine, max_wait_ms=bucketed_wait_ms, queue_limit=1024,
                    mode=mode,
                ) as b:
                    rep = open_loop(
                        b, images, rate_rps=open_rate,
                        num_requests=open_requests, seed=0,
                    )
                rep["mode_admission"] = mode
                rep["engine"] = engine_delta(before, engine.stats())
                return rep
            return run

        leg("partial_open_bucketed", open_leg("bucketed"))
        leg("partial_open_continuous", open_leg("continuous"))

    headline = None
    if "error" not in bucketed and "error" not in continuous:
        headline = {
            "continuous_over_bucketed_rps": round(
                continuous["throughput_rps"]
                / max(1e-9, bucketed["throughput_rps"]), 3
            ),
            "p99_ms_bucketed": bucketed["latency_ms"]["p99"],
            "p99_ms_continuous": continuous["latency_ms"]["p99"],
            "p99_matched": bool(
                continuous["latency_ms"]["p99"]
                <= bucketed["latency_ms"]["p99"]
            ),
        }

    # ---- leg 2: persisted-AOT cold start (two REAL fresh processes) --
    def cold_start_leg():
        # a PRIVATE jax HLO cache shared by both children isolates the
        # comparison: child 1 pays real compiles (cold everything) and
        # stores the AOT blobs; child 2 deserializes by fingerprint.  (A
        # JAX_COMPILATION_CACHE_DIR set from outside is never overridden:
        # the children then share that one.)
        # The leg gets its OWN empty AOT store — the session-wide
        # `aot_dir` was already populated by leg 1's warmup, and a
        # pre-warmed store would hand the "cold" child a millisecond
        # load, deleting the very compile-seconds drop being measured.
        jax_cache = os.path.join(serve_events_root, "jax-cache")
        leg_aot_dir = os.path.join(serve_events_root, "serve-aot-coldleg")
        out = {}
        for tag in ("cold", "warm"):
            env = dict(os.environ, JAX_PLATFORMS=platform)
            env.setdefault("JAX_COMPILATION_CACHE_DIR", jax_cache)
            child_dir = os.path.join(serve_events_root, f"version-{tag}")
            t0 = time.perf_counter()
            proc = subprocess.run(
                [
                    sys.executable, os.path.join(repo, "bench.py"),
                    "--serve-cold-child", child_dir, leg_aot_dir,
                ],
                cwd=repo, env=env, capture_output=True, text=True,
                timeout=600,
            )
            wall = time.perf_counter() - t0
            if proc.returncode != 0:
                raise RuntimeError(
                    f"cold-start child ({tag}) rc={proc.returncode}: "
                    f"{(proc.stderr or '')[-800:]}"
                )
            child = json.loads(proc.stdout.strip().splitlines()[-1])
            child["process_wall_s"] = round(wall, 2)
            # judge the stream, not the child's self-report: compile
            # events in this child's version dir
            caches = []
            from distributed_training_comparison_tpu.obs import load_events

            for ev in load_events(
                os.path.join(child_dir, "events.jsonl")
            ):
                if ev.get("kind") == "compile":
                    caches.append((ev.get("payload") or {}).get("cache"))
            child["stream_compile_caches"] = caches
            out[tag] = child
        real_compiles_in_warm = sum(
            1 for c in out["warm"]["stream_compile_caches"]
            if c != "persisted"
        )
        real_compiles_in_cold = sum(
            1 for c in out["cold"]["stream_compile_caches"]
            if c != "persisted"
        )
        out["summary"] = {
            "cold_warmup_s": out["cold"]["warmup_s"],
            "warm_warmup_s": out["warm"]["warmup_s"],
            "warmup_speedup": round(
                out["cold"]["warmup_s"] / max(1e-9, out["warm"]["warmup_s"]),
                2,
            ),
            "compile_s_cold": out["cold"]["compile_s"],
            "load_s_warm": out["warm"]["compile_s"],
            "compile_s_drop": round(
                out["cold"]["compile_s"] - out["warm"]["compile_s"], 3
            ),
            # the acceptance bar: the restarted replica compiled NOTHING
            "real_compile_events_in_warm_stream": real_compiles_in_warm,
            "persisted_hits_warm": out["warm"]["persisted_hits"],
        }
        if real_compiles_in_warm:
            raise RuntimeError(
                f"persisted-AOT cold start leaked {real_compiles_in_warm} "
                "real compile(s) in the restarted replica's stream"
            )
        if not real_compiles_in_cold:
            # a "cold" child that compiled nothing measured nothing: the
            # leg's AOT store leaked pre-warmed blobs (the bug this guard
            # pins) and the drop above would be vacuously zero
            raise RuntimeError(
                "cold-start child paid no real compile — its AOT store "
                "was not empty, so the leg measured no drop"
            )
        return out

    leg("cold_start", cold_start_leg)

    # ---- legs 3+4: router scale-out + SLO classes --------------------
    def router_leg(n_replicas):
        def run():
            # arm_sentinel=False + monitor= on the router: replica
            # warmup compiles (e.g. a store-verify-rejected AOT blob)
            # must not land as recompile-storm flags in the committed
            # ledger — same arming design serve_main uses
            r = ServeRouter(
                lambda rid: ServeEngine(
                    model_name=model_name, buckets=buckets,
                    precision="bf16", image_size=image_size,
                    monitor=monitor, aot_cache=aot_cache,
                    arm_sentinel=False,
                ),
                replicas=n_replicas, bus=bus, registry=registry,
                emit_every_s=2.0, queue_limit=1024, monitor=monitor,
            )
            try:
                r.warmup()
                rep = closed_loop(
                    r, images, num_requests=router_requests,
                    concurrency=router_conc,
                )
            finally:
                r.close()
            rep["router"] = r.stats()
            return rep
        return run

    r1 = leg("router_1_replica", router_leg(1))
    r2 = leg("router_2_replicas", router_leg(2))
    router_summary = None
    if "error" not in r1 and "error" not in r2:
        router_summary = {
            "scale_out_rps_ratio": round(
                r2["throughput_rps"] / max(1e-9, r1["throughput_rps"]), 3
            ),
            "replica_warm_starts_from_persisted": r2["router"]["engine"][
                "persisted_hits"
            ],
        }

    def slo_leg():
        classes = parse_slo_classes(
            "gold:priority=0:deadline_ms=10000:target=0.9,"
            "bulk:priority=2"
        )
        r = ServeRouter(
            lambda rid: ServeEngine(
                model_name=model_name, buckets=buckets,
                precision="bf16", image_size=image_size,
                monitor=monitor, aot_cache=aot_cache,
                arm_sentinel=False,
            ),
            replicas=1, classes=classes, bus=bus, registry=registry,
            emit_every_s=1.0, queue_limit=1024, monitor=monitor,
        )
        try:
            r.warmup()
            rep = mixed_tenants(
                r, images,
                tenants={
                    "gold": {"rate_rps": 16.0,
                             "num_requests": open_requests // 2},
                    "bulk": {"rate_rps": 16.0,
                             "num_requests": open_requests // 2},
                },
                seed=0,
            )
        finally:
            r.close()
        rep["classes"] = r.metrics.class_payload()
        return rep

    leg("slo_mixed_tenants", slo_leg)

    registry.flush(bus)  # per-bucket exec/... dispatch sketches → stream
    obs.reset(bus)

    # the leg's self-checks: schema + required kinds, and the per-class
    # SLO attainment gate reconstructed from the stream alone
    check_rc = events_check_rc(
        serve_events_root, require_kinds=("compile", "serve_route")
    )
    serve_gate_rc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "run_report.py"),
         serve_events_root, "--serve"],
    ).returncode

    record = {
        "metric": "cifar100_resnet18_serve",
        "version": 2,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "model": model_name,
        "precision": "bf16",
        "buckets": list(buckets),
        "bucketed_wait_ms": bucketed_wait_ms,
        "closed_concurrency": closed_conc,
        "open_rate_rps": round(open_rate, 2) if open_rate else None,
        "warmup_compile_s": round(warmup_s, 2),
        "continuous_vs_bucketed": headline,
        "router_scale_out": router_summary,
        "compile_ledger": monitor.ledger(),
        "events_check_rc": check_rc,
        "run_report_serve_rc": serve_gate_rc,
        "legs": legs,
        "note": (
            "CPU capture: one shared core set — the router scale-out "
            "leg is informational (replicas contend for the same "
            "silicon, parity expected; the leg pins routing overhead), "
            "and absolute latencies are CPU service times.  The "
            "continuous-vs-bucketed ordering and the cold-start "
            "compile-seconds drop bind; the bucketed baseline's window "
            f"is {bucketed_wait_ms} ms (tuned long enough to actually "
            "fill buckets at partial load — the tail cliff being "
            "measured)."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "metric": record["metric"],
        "platform": platform,
        "events_check_rc": check_rc,
        "run_report_serve_rc": serve_gate_rc,
        "continuous_vs_bucketed": headline,
        "cold_start": (legs.get("cold_start") or {}).get("summary"),
        "router_scale_out": router_summary,
        "full_record": out_path,
    }))
    return record


def bench_serve_fleet(out_path: str = "BENCH_SERVE_FLEET.json") -> dict:
    """The PROCESS fleet's scoreboard (``--serve-fleet``): every replica
    a real OS process behind the socket transport (serve/fleet/).

    Five legs, one committed JSON capture:

    1-3. **fleet capacity at 1/2/4 process replicas** — closed-loop
       saturation through the router's dispatcher threads.  On this
       CPU host the replicas still share one core set, so the speedup
       that CAN appear is pipelining: replica B's compute overlaps the
       router-side gaps (batch assembly, socket round-trip, future
       resolution) that leave a single worker idle between dispatches.
       The thread-transport baseline (BENCH_SERVE.json router leg) had
       NO such overlap to claim — its 2-replica ratio sat below 1.
    4. **scale up/down** — a flash then a trickle through the live
       autoscaler: the G/G/m sizing must grow the fleet under the
       flash and drain it back on the trickle, both directions visible
       as applied ``serve_scale`` events, with ``run_report --serve``'s
       scale/fleet agreement gate as the leg's self-check.
    5. **replica kill** — SIGKILL one worker mid-backlog: the in-flight
       batch requeues, the supervisor relaunches from the shared
       persisted AOT store, and every admitted request completes (zero
       ``failed``).

    Weights are fresh-initialized; sized down so the capture reproduces
    on the CI host.  Each leg gets its own event root + fleet dir; the
    AOT store is shared capture-wide so later spawns warm-start.
    """
    import os
    import signal
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.serve import (
        ServeRouter,
        closed_loop,
        open_loop,
        request_pool,
    )
    from distributed_training_comparison_tpu.serve.fleet import (
        Autoscaler,
        parse_scale_targets,
        worker_hparams_dict,
    )

    platform = _require_explicit_cpu("--serve-fleet")
    repo = os.path.dirname(os.path.abspath(__file__))
    # small images + a short ladder ON PURPOSE: the per-dispatch compute
    # must be small enough that the router-side overhead a second
    # process replica can hide (assembly/socket/resolve) is a visible
    # fraction of the cycle — at 224px the capture would only restate
    # "compute dominates"
    model_name, image_size = "resnet18", 16
    buckets = (1, 4)
    fleet_requests, fleet_conc, fleet_reps = 192, 16, 3
    kill_requests = 240

    root = tempfile.mkdtemp(prefix="serve-fleet-bench-")
    aot_dir = os.path.join(root, "serve-aot")
    legs: dict = {}

    def leg(key, fn):
        try:
            legs[key] = fn()
        except Exception as e:  # evidence over abort, like run_legs
            legs[key] = {"error": f"{type(e).__name__}: {e}"[:300]}
        emit_progress(key, legs[key])
        return legs[key]

    def leg_setup(name):
        leg_root = os.path.join(root, name)
        os.makedirs(leg_root, exist_ok=True)
        bus = obs.configure(run_id=obs.new_run_id())
        bus.bind_dir(leg_root)
        hp = load_config("single", argv=[
            "--model", model_name, "--image-size", str(image_size),
            "--serve-buckets", ",".join(str(b) for b in buckets),
            "--seed", "3", "--ckpt-path", leg_root,
        ])
        spec = {
            "fleet_dir": os.path.join(leg_root, "serve-fleet"),
            "events_dir": leg_root,
            "hparams": worker_hparams_dict(hp),
            "port_base": 0,  # ephemeral; the handshake reports the port
            "metrics_port_base": 0,
            "platform": platform,
            "run_id": bus.run_id,
            "attempt": 0,
            "aot_dir": aot_dir,
        }
        return leg_root, bus, spec

    def lat(rep):
        return {
            "throughput_rps": rep["throughput_rps"],
            "p50_ms": rep["latency_ms"]["p50"],
            "p99_ms": rep["latency_ms"]["p99"],
        }

    # ---- legs 1-3: capacity at 1/2/4 process replicas -----------------
    def capacity_leg(n):
        def run():
            leg_root, bus, spec = leg_setup(f"fleet_{n}")
            # per-leg request-pool fold: sibling legs must not replay
            # byte-identical pools (the exporter-collision satellite's
            # decorrelation path, exercised where it matters)
            pool = request_pool(
                256, image_size=image_size, seed=0, fold=("fleet", n)
            )
            r = ServeRouter(
                None, replicas=n, transport="process", process_spec=spec,
                bus=bus, queue_limit=1024, emit_every_s=2.0,
            )
            try:
                if not r.wait_ready(n=n, timeout=900):
                    raise RuntimeError(f"{n}-replica fleet never went ready")
                reps = [
                    closed_loop(
                        r, pool, num_requests=fleet_requests,
                        concurrency=fleet_conc,
                    )
                    for _ in range(fleet_reps)
                ]
            finally:
                r.close()
            obs.reset(bus)
            med = sorted(
                reps, key=lambda x: x["throughput_rps"]
            )[len(reps) // 2]
            return {
                "replicas": n,
                "median": lat(med),
                "reps": [lat(x) for x in reps],
                "events_check_rc": events_check_rc(
                    leg_root, require_kinds=("replica", "serve_route")
                ),
            }
        return run

    f1 = leg("fleet_1", capacity_leg(1))
    f2 = leg("fleet_2", capacity_leg(2))
    f4 = leg("fleet_4", capacity_leg(4))
    summary = None
    if all("error" not in x for x in (f1, f2, f4)):
        rps1 = f1["median"]["throughput_rps"]
        summary = {
            "throughput_rps": {
                1: rps1,
                2: f2["median"]["throughput_rps"],
                4: f4["median"]["throughput_rps"],
            },
            "process_scale_ratio_2v1": round(
                f2["median"]["throughput_rps"] / max(1e-9, rps1), 3
            ),
            "process_scale_ratio_4v1": round(
                f4["median"]["throughput_rps"] / max(1e-9, rps1), 3
            ),
            "thread_baseline_2v1": _thread_baseline_ratio(repo),
        }

    # ---- leg 4: autoscaler up AND down on live traffic ----------------
    def scale_leg():
        leg_root, bus, spec = leg_setup("scale_up_down")
        pool = request_pool(
            256, image_size=image_size, seed=0, fold=("fleet", "scale")
        )
        r = ServeRouter(
            None, replicas=1, transport="process", process_spec=spec,
            bus=bus, queue_limit=4096, emit_every_s=1.0,
        )
        # target 2000ms, NOT a tight one: on this 1-core host the
        # flash-era service p99 is contention-inflated (workers + router
        # share the core), and service sketches are session-cumulative —
        # a tight target would read that noise as "m=1 can never hold"
        # and refuse to scale down.  The flash still forces scale-up
        # through saturation (rho >= 1 -> predicted tail = inf at m=1)
        # at ANY finite target, so both directions stay honest.
        scaler = Autoscaler(
            r.metrics, parse_scale_targets("p99=2000"),
            min_replicas=1, max_replicas=2,
            window_s=6.0, cooldown_s=3.0, hold=2, bus=bus,
        )
        r.attach_autoscaler(scaler)
        r._scale_every_s = 0.5  # capture-speed ticks, same math
        rps1 = (
            (legs.get("fleet_1") or {}).get("median") or {}
        ).get("throughput_rps") or 8.0
        try:
            if not r.wait_ready(n=1, timeout=900):
                raise RuntimeError("scale leg's first replica not ready")
            # flash well past one replica's measured capacity: the
            # G/G/m fit saturates and the scaler must grow the fleet
            flash_rate = max(8.0, 2.5 * rps1)
            flash = open_loop(
                r, pool, rate_rps=flash_rate,
                num_requests=int(flash_rate * 8), seed=1,
            )
            # trickle until the 6s arrival window forgets the flash and
            # the scaler drains back down (bounded: 4 bursts)
            trickles = []
            for burst in range(4):
                trickles.append(open_loop(
                    r, pool, rate_rps=2.0, num_requests=24,
                    seed=2 + burst,
                ))
                if r.active_replicas() == 1:
                    break
            scaled_down_live = r.active_replicas() == 1
        finally:
            r.close()
        obs.reset(bus)
        scale_events = [
            (e.get("payload") or {})
            for e in obs.load_events(os.path.join(leg_root, "events.jsonl"))
            if e.get("kind") == "serve_scale"
        ]
        ups = [
            p for p in scale_events
            if p.get("scale_state", p.get("state")) == "applied"
            and p.get("added")
        ]
        downs = [
            p for p in scale_events
            if p.get("scale_state", p.get("state")) == "applied"
            and p.get("drained")
        ]
        out = {
            "flash": lat(flash),
            "trickle_bursts": len(trickles),
            "scaled_down_live": scaled_down_live,
            "scale_up_applied": len(ups),
            "scale_down_applied": len(downs),
            "sized_by": sorted({
                p.get("sized_by") for p in ups + downs if p.get("sized_by")
            }),
            "events_check_rc": events_check_rc(
                leg_root,
                require_kinds=("replica", "serve_route", "serve_scale"),
            ),
            # the satellite gate: scale decisions and replica lifecycles
            # must AGREE on the stream run_report --serve reconstructs
            "run_report_serve_rc": subprocess.run(
                [sys.executable,
                 os.path.join(repo, "tools", "run_report.py"),
                 leg_root, "--serve"],
            ).returncode,
        }
        if not ups or not downs:
            raise RuntimeError(
                f"autoscaler evidence incomplete: {len(ups)} scale-up / "
                f"{len(downs)} scale-down applied events "
                f"(states seen: {sorted({p.get('state') for p in scale_events})})"
            )
        return out

    leg("scale_up_down", scale_leg)

    # ---- leg 5: SIGKILL a worker mid-backlog --------------------------
    def kill_leg():
        leg_root, bus, spec = leg_setup("replica_kill")
        pool = request_pool(
            256, image_size=image_size, seed=0, fold=("fleet", "kill")
        )
        r = ServeRouter(
            None, replicas=2, transport="process", process_spec=spec,
            bus=bus, queue_limit=1024, emit_every_s=1.0,
        )
        try:
            if not r.wait_ready(n=2, timeout=900):
                raise RuntimeError("kill leg's fleet never went ready")
            victim = r.replicas[0]
            pid = victim.pid
            futs = [
                r.submit(pool[i % len(pool)]) for i in range(kill_requests)
            ]
            deadline = time.monotonic() + 120
            while victim.dispatches < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            os.kill(pid, signal.SIGKILL)
            rows = [f.result(timeout=600) for f in futs]
            completed = len(rows)
            restarts = victim.restarts
            failed = r.metrics.failed
            shed = r.metrics.shed
            expired = r.metrics.expired
        finally:
            r.close()
        obs.reset(bus)
        out = {
            "requests": kill_requests,
            "completed": completed,
            "failed": failed,
            "shed": shed,
            "expired": expired,
            "supervisor_restarts": restarts,
            "events_check_rc": events_check_rc(
                leg_root, require_kinds=("replica", "serve_route")
            ),
        }
        if failed or completed != kill_requests:
            raise RuntimeError(
                f"replica kill dropped work: {completed}/{kill_requests} "
                f"completed, {failed} failed"
            )
        return out

    leg("replica_kill", kill_leg)

    check_rcs = [
        v.get("events_check_rc") for v in legs.values() if isinstance(v, dict)
    ]
    all_checks_ok = bool(check_rcs) and all(rc == 0 for rc in check_rcs)
    record = {
        "metric": "cifar100_resnet18_serve_fleet",
        "version": 1,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "model": model_name,
        "image_size": image_size,
        "buckets": list(buckets),
        "closed_concurrency": fleet_conc,
        "requests_per_rep": fleet_requests,
        "reps_per_fleet_size": fleet_reps,
        "fleet_capacity": summary,
        "all_events_checks_ok": all_checks_ok,
        "legs": legs,
        "note": (
            "CPU capture, one shared core set: the 2v1 ratio's MAGNITUDE "
            "is not the paper's accelerator claim — what binds is the "
            "ORDERING (process replicas pipeline the router-side gaps a "
            "single worker idles through, so 2v1 > 1 where the thread "
            "transport's baseline sat below 1) plus the zero-loss kill "
            "leg and both autoscale directions on live traffic.  "
            "Absolute latencies are 1-core service times at 16px."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "metric": record["metric"],
        "platform": platform,
        "fleet_capacity": summary,
        "scale_up_down": {
            k: (legs.get("scale_up_down") or {}).get(k)
            for k in ("scale_up_applied", "scale_down_applied",
                      "run_report_serve_rc", "error")
        },
        "replica_kill": {
            k: (legs.get("replica_kill") or {}).get(k)
            for k in ("completed", "failed", "supervisor_restarts", "error")
        },
        "all_events_checks_ok": all_checks_ok,
        "full_record": out_path,
    }))
    return record


def _thread_baseline_ratio(repo):
    """The thread transport's 2-replica ratio from the committed
    BENCH_SERVE.json — the number this capture's process ratio is read
    against (None when the baseline capture is absent)."""
    import os

    try:
        with open(os.path.join(repo, "BENCH_SERVE.json")) as f:
            return ((json.load(f).get("router_scale_out") or {})
                    .get("scale_out_rps_ratio"))
    except (OSError, ValueError):
        return None


def _bench_serve_cold_child(argv) -> None:
    """One REAL fresh serving process for the cold-start leg: build the
    engine against the given persisted AOT store, warm the ladder, serve
    a smoke batch, print one JSON line.  ``argv = [events_dir,
    aot_cache_dir]``.  Every compile/load lands as a ``compile`` event
    in ``events_dir`` — the parent judges the STREAM, not this report."""
    import os

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.serve import ServeEngine
    from distributed_training_comparison_tpu.utils import PersistedServeCache

    events_dir, aot_dir = argv[0], argv[1]
    t_start = time.perf_counter()
    bus = obs.configure(run_id=obs.new_run_id())
    bus.bind_dir(events_dir)
    registry = obs.MetricRegistry()
    monitor = obs.CompileMonitor(bus=bus, registry=registry)
    engine = ServeEngine(
        model_name="resnet18",
        buckets=(1, 8),
        precision="bf16",
        image_size=32,
        monitor=monitor,
        aot_cache=PersistedServeCache(aot_dir),
    )
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    # first response: the reason cold start matters
    t0 = time.perf_counter()
    engine.predict_logits(np.zeros((3, 32, 32, 3), np.uint8))
    first_response_s = time.perf_counter() - t0
    registry.flush(bus)
    ledger = monitor.ledger()
    print(json.dumps({
        "warmup_s": round(warmup_s, 3),
        "first_response_s": round(first_response_s, 3),
        "init_to_first_response_s": round(
            time.perf_counter() - t_start, 3
        ),
        "compiles": engine.stats()["compiles"],
        "persisted_hits": engine.stats()["persisted_hits"],
        "compile_s": round(sum(r["compile_s"] for r in ledger), 3),
        "caches": [r["cache"] for r in ledger],
    }))
    obs.reset(bus)


def events_check_rc(ckpt_root: str, require_kinds=()) -> int:
    """Self-validate a bench capture: ``tools/run_report.py --check`` over
    every ``events*.jsonl`` the run left behind, returncode recorded in the
    committed JSON (0 = every record parses against the versioned obs
    schema) — nobody trusts the numbers of a capture that doesn't.
    ``require_kinds`` additionally fails the check unless the stream
    carries those kinds: the resilience/serve legs require ``compile``
    events, so a silently-degraded compile hook can't commit a capture
    whose ledger is missing."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(repo, "tools", "run_report.py"),
           ckpt_root, "--check"]
    for kind in require_kinds or ():
        cmd += ["--require-kind", kind]
    return subprocess.run(cmd).returncode


def bench_trace(out_path: str = "BENCH_TRACE.json") -> dict:
    """Request tracing's scoreboard (``--trace``): what the rail costs
    on the hot path and what it buys on the process fleet.

    Four legs, one committed JSON capture:

    1. **hotpath** — the tracer's per-request work in isolation (mint +
       enqueue + batch header + finish), batches of 8, at sampling 0
       (context only, nothing kept) and 1.0 (every span tree serialized
       to a real event file).  The sampling-0 number is the tax every
       healthy request pays and gates the 25 µs/request budget; the 1.0
       number is the ceiling nobody runs at.
    2. **fleet_tail** — sampling 0 on a real 1-process fleet: probe the
       warm latency, then breach half of it under load.  Every breached
       or queue-expired request must come back with a kept trace, and
       ``run_report --trace`` must reconstruct it (device span included,
       retro-flushed from the worker ring) with exit 0.
    3. **fleet_full** — sampling 1.0 with the live autoscaler attached:
       every ``serve_scale`` decision carries the Sakasegawa-modeled
       wait NEXT TO the trace-measured one; the capture records both so
       the model's drift is a number, not a vibe.
    4. **kill_requeue** — SIGKILL one of two workers mid-backlog at
       sampling 0: the rescued request keeps ONE trace spanning both
       replicas with the failed attempt annotated ``requeued``.

    Every fleet leg self-validates via ``run_report --check
    --require-kind trace`` over the files it leaves behind.
    """
    import os
    import signal
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.serve import (
        ServeRouter,
        open_loop,
        request_pool,
    )
    from distributed_training_comparison_tpu.serve.batcher import (
        DeadlineExceeded,
        ServeFuture,
    )
    from distributed_training_comparison_tpu.serve.fleet import (
        Autoscaler,
        parse_scale_targets,
        worker_hparams_dict,
    )

    platform = _require_explicit_cpu("--trace")
    repo = os.path.dirname(os.path.abspath(__file__))
    model_name, image_size = "resnet18", 16
    buckets = (1, 4)
    budget_us = 25.0

    root = tempfile.mkdtemp(prefix="trace-bench-")
    aot_dir = os.path.join(root, "serve-aot")
    legs: dict = {}

    def leg(key, fn):
        try:
            legs[key] = fn()
        except Exception as e:
            legs[key] = {"error": f"{type(e).__name__}: {e}"[:300]}
        emit_progress(key, legs[key])
        return legs[key]

    def leg_setup(name, sample):
        leg_root = os.path.join(root, name)
        os.makedirs(leg_root, exist_ok=True)
        bus = obs.configure(run_id=obs.new_run_id())
        bus.bind_dir(leg_root)
        hp = load_config("single", argv=[
            "--model", model_name, "--image-size", str(image_size),
            "--serve-buckets", ",".join(str(b) for b in buckets),
            "--seed", "3", "--ckpt-path", leg_root,
        ])
        spec = {
            "fleet_dir": os.path.join(leg_root, "serve-fleet"),
            "events_dir": leg_root,
            "hparams": worker_hparams_dict(hp),
            "port_base": 0,
            "metrics_port_base": 0,
            "platform": platform,
            "run_id": bus.run_id,
            "attempt": 0,
            "aot_dir": aot_dir,
        }
        tracer = obs.RequestTracer(bus=bus, sample_rate=sample, seed=3)
        return leg_root, bus, spec, tracer

    def trace_rc(leg_root):
        return subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "run_report.py"),
             leg_root, "--trace"],
        ).returncode

    # ---- leg 1: the hot path in isolation ----------------------------
    def hotpath_leg():
        n_batches, per_batch = 2000, 8

        def run(sample, bind_dir):
            bus = None
            if bind_dir is not None:
                bus = obs.EventBus(run_id=obs.new_run_id())
                bus.bind_dir(bind_dir)
            tr = obs.RequestTracer(bus=bus, sample_rate=sample, seed=3)
            img = np.zeros((1,), np.uint8)  # payload is not the cost
            t0 = time.perf_counter()
            for _ in range(n_batches):
                batch = []
                for _ in range(per_batch):
                    fut = ServeFuture(time.monotonic(), None, cls="default")
                    fut.trace = tr.begin("default")
                    tr.enqueued(fut.trace)
                    fut.trace.t_taken = time.monotonic()
                    batch.append((img, fut))
                bsid = tr.batch_begin(batch, 0)
                tr.wire_header(batch, bsid, 0)
                tr.batch_end(batch, bsid, device_s=0.001)
                for _, fut in batch:
                    fut.set_result(img)
                    tr.finish(fut, "completed")
            per_req_us = (
                (time.perf_counter() - t0) / (n_batches * per_batch) * 1e6
            )
            if bus is not None:
                bus.close()
            return round(per_req_us, 3)

        # warm both paths once so neither sample pays first-call costs
        run(0.0, None), run(1.0, os.path.join(root, "hot-warm"))
        off = run(0.0, os.path.join(root, "hot-0"))
        full = run(1.0, os.path.join(root, "hot-1"))
        out = {
            "requests": n_batches * per_batch,
            "batch_size": per_batch,
            "per_request_us_sample_0": off,
            "per_request_us_sample_1": full,
            "budget_us": budget_us,
            "within_budget": off <= budget_us,
        }
        if not out["within_budget"]:
            raise RuntimeError(
                f"tracer hot path {off}us/request blows the "
                f"{budget_us}us budget"
            )
        return out

    leg("hotpath", hotpath_leg)

    # ---- leg 2: tail-kept breaches on a real fleet -------------------
    def tail_leg():
        leg_root, bus, spec, tracer = leg_setup("fleet_tail", 0.0)
        pool = request_pool(
            64, image_size=image_size, seed=0, fold=("trace", "tail")
        )
        r = ServeRouter(
            None, replicas=1, transport="process", process_spec=spec,
            bus=bus, queue_limit=1024, emit_every_s=1.0, tracer=tracer,
        )
        try:
            if not r.wait_ready(n=1, timeout=900):
                raise RuntimeError("tail leg's fleet never went ready")
            t0 = time.perf_counter()
            for i in range(8):  # healthy warm traffic: must keep nothing
                r.submit(pool[i]).result(timeout=600)
            probe_ms = (time.perf_counter() - t0) / 8 * 1e3
            deadline_ms = max(2.0, probe_ms * 0.5)
            futs = [
                r.submit(pool[i % len(pool)], deadline_ms=deadline_ms)
                for i in range(24)
            ]
            breached = expired = 0
            for f in futs:
                try:
                    f.result(timeout=600)
                    breached += 0 if f.within_deadline else 1
                except DeadlineExceeded:
                    expired += 1
        finally:
            r.close()
        obs.reset(bus)
        out = {
            "probe_ms": round(probe_ms, 2),
            "deadline_ms": round(deadline_ms, 2),
            "breached": breached,
            "expired": expired,
            "kept": tracer.kept,
            "kept_by_reason": dict(tracer.kept_by_reason),
            "healthy_dropped": tracer.dropped,
            "events_check_rc": events_check_rc(
                leg_root, require_kinds=("trace", "serve_route")
            ),
            "run_report_trace_rc": trace_rc(leg_root),
        }
        if breached + expired == 0:
            raise RuntimeError("tail leg produced no deadline pressure")
        if tracer.kept < breached + expired:
            raise RuntimeError(
                f"tail keep missed work: {tracer.kept} kept < "
                f"{breached} breached + {expired} expired"
            )
        return out

    leg("fleet_tail", tail_leg)

    # ---- leg 3: sample 1.0 + autoscaler wait drift -------------------
    def full_leg():
        leg_root, bus, spec, tracer = leg_setup("fleet_full", 1.0)
        pool = request_pool(
            64, image_size=image_size, seed=0, fold=("trace", "full")
        )
        r = ServeRouter(
            None, replicas=1, transport="process", process_spec=spec,
            bus=bus, queue_limit=1024, emit_every_s=1.0, tracer=tracer,
        )
        scaler = Autoscaler(
            r.metrics, parse_scale_targets("p99=2000"),
            min_replicas=1, max_replicas=2,
            window_s=6.0, cooldown_s=3.0, hold=2, bus=bus,
        )
        r.attach_autoscaler(scaler)
        r._scale_every_s = 0.5
        try:
            if not r.wait_ready(n=1, timeout=900):
                raise RuntimeError("full leg's fleet never went ready")
            t0 = time.perf_counter()
            for i in range(8):
                r.submit(pool[i]).result(timeout=600)
            warm_s = (time.perf_counter() - t0) / 8
            # OPEN loop below one replica's capacity: a closed loop
            # would pin utilization at 1 and the modeled wait at
            # infinity — the drift comparison needs a finite model
            rate = min(8.0, max(2.0, 0.4 / warm_s))
            rep = open_loop(
                r, pool, rate_rps=rate,
                num_requests=max(48, int(rate * 10)), seed=1,
            )
        finally:
            r.close()
        obs.reset(bus)
        waits = [
            {
                "modeled_s": p.get("wait_modeled_s"),
                "measured_s": p.get("wait_measured_s"),
            }
            for e in obs.load_events(os.path.join(leg_root, "events.jsonl"))
            if e.get("kind") == "serve_scale"
            for p in [e.get("payload") or {}]
            if "wait_measured_s" in p
        ]
        both = [
            w for w in waits
            if w["measured_s"] is not None and w["modeled_s"] is not None
        ]
        drift = None
        if both:
            drift = round(
                both[-1]["measured_s"]["p50"] - both[-1]["modeled_s"], 6
            )
        out = {
            "requests": rep["completed"],
            "open_loop_rate_rps": round(rate, 2),
            "sampled_p50_ms": rep["latency_ms"]["p50"],
            "sampled_p99_ms": rep["latency_ms"]["p99"],
            "kept": tracer.kept,
            "scale_decisions_with_wait": len(waits),
            "wait_last": both[-1] if both else (waits[-1] if waits else None),
            "wait_drift_p50_vs_model_s": drift,
            "events_check_rc": events_check_rc(
                leg_root, require_kinds=("trace", "serve_scale")
            ),
            "run_report_trace_rc": trace_rc(leg_root),
        }
        if not both:
            raise RuntimeError(
                "no serve_scale decision carried a measured wait next to "
                "a finite modeled one"
            )
        return out

    leg("fleet_full", full_leg)

    # ---- leg 4: one trace across a kill-requeue ----------------------
    def kill_leg():
        leg_root, bus, spec, tracer = leg_setup("kill_requeue", 0.0)
        pool = request_pool(
            64, image_size=image_size, seed=0, fold=("trace", "kill")
        )
        r = ServeRouter(
            None, replicas=2, transport="process", process_spec=spec,
            bus=bus, queue_limit=1024, emit_every_s=1.0, tracer=tracer,
        )
        try:
            if not r.wait_ready(n=2, timeout=900):
                raise RuntimeError("kill leg's fleet never went ready")
            victim = r.replicas[0]
            pid = victim.pid
            futs = [r.submit(pool[i % len(pool)]) for i in range(96)]
            deadline = time.monotonic() + 120
            while victim.dispatches < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            os.kill(pid, signal.SIGKILL)
            completed = len([f.result(timeout=600) for f in futs])
            failed = r.metrics.failed
        finally:
            r.close()
        obs.reset(bus)
        sys.path.insert(0, os.path.join(repo, "tools"))
        import run_report as _rr

        events = []
        for f in _rr.find_event_files(leg_root):
            events.extend(obs.load_events(f))
        requeued = [
            row for row in _rr.trace_rows(events)
            if row["keep"] == "requeued"
        ]
        out = {
            "requests": 96,
            "completed": completed,
            "failed": failed,
            "requeued_traces": len(requeued),
            "one_trace_spans_both_replicas": bool(
                requeued and len(requeued[0]["rids"]) >= 2
            ),
            "events_check_rc": events_check_rc(
                leg_root, require_kinds=("trace", "replica")
            ),
            "run_report_trace_rc": trace_rc(leg_root),
        }
        if not requeued:
            raise RuntimeError("kill-requeued request kept no trace")
        return out

    leg("kill_requeue", kill_leg)

    check_rcs = [
        v.get("events_check_rc") for v in legs.values()
        if isinstance(v, dict) and "events_check_rc" in v
    ]
    trace_rcs = [
        v.get("run_report_trace_rc") for v in legs.values()
        if isinstance(v, dict) and "run_report_trace_rc" in v
    ]
    record = {
        "metric": "cifar100_resnet18_request_tracing",
        "version": 1,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "model": model_name,
        "image_size": image_size,
        "buckets": list(buckets),
        "budget_us_per_request": budget_us,
        "all_events_checks_ok": bool(check_rcs)
        and all(rc == 0 for rc in check_rcs),
        "all_trace_reports_ok": bool(trace_rcs)
        and all(rc == 0 for rc in trace_rcs),
        "legs": legs,
        "note": (
            "CPU capture: absolute latencies are 1-core service times at "
            "16px and the wait-drift magnitude reflects core contention, "
            "not the paper's accelerator claim.  What binds: the "
            "sampling-0 hot path under the 25us/request budget, every "
            "breached/expired/requeued request reconstructable from "
            "event files alone (exit-0 --trace reports), and modeled "
            "vs measured queue wait recorded side by side."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(record))
    return record


def _drive_fleet_gauntlet(
    ckpt_root: str, proc, driver_log: list, readmit,
    timeout: float = 600.0,
) -> None:
    """The external environment's script, shared by the resilience and
    chaos legs: SIGKILL host 1 (spot reclaim) once attempt 0 has a
    verified checkpoint, and — with ``readmit`` — signal re-admission
    once the shrunk attempt's ``run_start`` lands: ``True`` writes
    ``host-1.up`` directly (the legacy scheduler interface),
    ``"probe"`` only creates the ``--fleet-probe`` ready file and lets
    the SchedulerProbe write the marker itself.  Never an operator
    action: no ``host-i.down`` is ever written here."""
    import os
    import signal as _signal
    import time as _time

    from distributed_training_comparison_tpu.resilience import read_manifest

    status_path = os.path.join(ckpt_root, "fleet", "status.json")
    events_path = os.path.join(ckpt_root, "version-0", "events.jsonl")

    def status():
        with open(status_path) as f:
            return json.load(f)

    def wait(cond, what) -> bool:
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if proc.poll() is not None:
                driver_log.append(f"fleet exited before {what}")
                return False
            try:
                if cond():
                    return True
            except (OSError, ValueError, KeyError):
                pass
            _time.sleep(0.05)
        driver_log.append(f"timed out waiting for {what}")
        return False

    if not wait(
        lambda: status()["attempt"] == 0
        and read_manifest(
            os.path.join(ckpt_root, "version-0", "last.ckpt")
        ) is not None,
        "attempt 0 checkpoint",
    ):
        return
    os.kill(int(status()["pids"]["1"]), _signal.SIGKILL)
    driver_log.append("spot-reclaimed host 1 (SIGKILL)")
    if not readmit:
        return
    if not wait(
        lambda: status()["attempt"] == 1
        and any(
            '"kind": "run_start"' in line and '"attempt": 1' in line
            for line in open(events_path).read().splitlines()
        ),
        "attempt 1 run_start",
    ):
        return
    if readmit == "probe":
        # the residue-closing path: the driver never touches
        # <ckpt>/fleet/ — it creates the PROBE's ready file (a k8s
        # node-ready / GCE guest-attribute stand-in) and --fleet-probe
        # turns that into host-1.up on the supervisor's own cadence
        with open(os.path.join(ckpt_root, "probe-ready-1"), "w"):
            pass
        driver_log.append(
            "scheduler marked host 1 schedulable (probe-ready-1)"
        )
        return
    with open(os.path.join(ckpt_root, "fleet", "host-1.up"), "w"):
        pass
    driver_log.append("scheduler re-admitted host 1 (host-1.up)")


def bench_resilience(out_path: str = "GOODPUT.json") -> dict:
    """The resilience leg: the ELASTIC-POOL gauntlet (ISSUE 10) — a real
    supervised 2-host fleet run through ``--supervise --fleet-hosts 2``
    that loses host 1 to a SIGKILL mid-run (shrink: the re-rendered
    world-size-1 attempt resumes from the verified checkpoint), re-admits
    it via the ``fleet/host-1.up`` marker (a deliberate
    drain-checkpoint-and-re-expand), and finishes at full width.  The
    supervisor's GOODPUT.json — goodput across every attempt plus the
    priced ``resize`` list — is the committed scoreboard; the capture
    self-validates with ``run_report --check --require-kind compile
    --require-kind resize``.

    Children are separate processes launched by the FleetSupervisor with
    re-rendered ``--world-size``/``--rank``/``--dist-url``, so the
    measured recovery cost includes everything a production relaunch pays:
    process start, imports, compile (persistent cache), restore.  Note
    the CPU emulation keeps rank 0's own device count constant across
    attempts, so DEVICE-count-changing reshard is not what this leg
    measures — that path is pinned end-to-end by tier-1's
    ``test_e2e_preempt_supervisor_elastic`` (8→4 devices, params
    allclose).  On CPU
    the child is ``tests/fleet_pool_worker.py`` (rank 0 trains for real;
    rank 1 is a pid+event-file host emulation — the pinned CI jax cannot
    run multi-process collectives on the CPU backend, see
    tests/test_multihost.py); on a TPU fleet the real
    ``src/tpu_jax/main.py`` entry serves, its ranks genuinely
    rendezvousing via ``init_distributed``.
    """
    import os
    import subprocess
    import sys
    import tempfile
    import threading

    platform = _require_explicit_cpu("--resilience")
    repo = os.path.dirname(os.path.abspath(__file__))
    ckpt_root = tempfile.mkdtemp(prefix="resilience-bench-")
    # CPU sizing (the only platform this mode runs on): tiny forced meshes
    # keep the per-child XLA compile tractable.  Epoch count is chosen so
    # productive step time dominates the three attempts' init/restore
    # overhead: the scoreboard must price the shrink/expand against a run
    # long enough to be worth resuming.
    child = os.path.join(repo, "tests", "fleet_pool_worker.py")
    size_args = [
        "--limit-examples", "4096", "--batch-size", "32", "--epoch", "150",
    ]

    cmd = [
        sys.executable, child, "--supervise",
        "--fleet-hosts", "2", "--fleet-local-devices", "1",
        "--fleet-grace-secs", "3", "--fleet-poll-secs", "0.2",
        "--synthetic-data", *size_args,
        "--ckpt-path", ckpt_root,
        "--save-last-min-secs", "0", "--no-progress",
        "--seed", "7", "--eval-step", "1000",
        "--device-chunk-steps", "8",
        "--heartbeat-secs", "0.5",
        "--goodput-json", out_path,
    ]

    driver_log: list = []

    def drive(proc) -> None:
        # kill host 1 once attempt 0 has a verified checkpoint; re-admit
        # it once the shrunk attempt is up (shared with the chaos leg)
        _drive_fleet_gauntlet(ckpt_root, proc, driver_log, readmit=True)

    proc = subprocess.Popen(
        cmd, cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    driver = threading.Thread(target=drive, args=(proc,), daemon=True)
    driver.start()
    out, err = proc.communicate()
    driver.join(timeout=10.0)
    emit_progress(
        "resilience_fleet",
        {"rc": proc.returncode, "driver": driver_log,
         "tail": (out or "")[-300:]},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"elastic-pool gauntlet failed (rc={proc.returncode}; driver: "
            f"{driver_log}): {(err or '')[-2000:]}"
        )

    # run_supervised wrote the aggregate (incl. the resize list) to
    # out_path; fold in the capture provenance + self-validation
    with open(out_path) as f:
        record = json.load(f)
    record["platform"] = platform
    record["gauntlet"] = {
        "fleet_hosts": 2,
        "script": "SIGKILL host 1 -> shrink to world 1 -> host-1.up -> "
                  "re-expand to world 2",
        "driver": driver_log,
    }
    # compile events required (PR 8: every attempt's executable ledger)
    # AND resize events (ISSUE 10: the shrink/expand must be priced) — a
    # silently-degraded hook can't commit a capture missing either
    record["events_check_rc"] = events_check_rc(
        ckpt_root, require_kinds=("compile", "resize")
    )
    from distributed_training_comparison_tpu.resilience.goodput import (
        write_goodput,
    )

    write_goodput(out_path, record)
    print(json.dumps({
        "metric": record["metric"],
        "events_check_rc": record["events_check_rc"],
        "goodput_frac": record["goodput_frac"],
        "productive_s": record["productive_s"],
        "total_wall_s": record["total_wall_s"],
        "restarts": record["restarts"],
        "preemptions": record["preemptions"],
        "attempts": record["attempts"],
        "resizes": [
            (r["from_world"], r["to_world"], r["reason"])
            for r in record.get("resizes", [])
        ],
        "platform": platform,
        "full_record": out_path,
    }))
    return record


def _run_serve_chaos_scenario(name: str, sc: dict, repo: str, run_report):
    """One ``session: "serve"`` chaos scenario: run the real ``--serve``
    entry (flash crowd onto an unwarmed bucket), judge the storm →
    sentinel alert → ``rewarm_serve`` → p99-recovery chain from the
    event stream alone.  Returns ``(row, problems, events_check_rc)``
    shaped like the fleet scenarios' rows."""
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.ops.policy import pending_actions
    from distributed_training_comparison_tpu.resilience import (
        check_chaos_expectations,
    )

    root = tempfile.mkdtemp(prefix=f"chaos-{name}-")
    cmd = [
        sys.executable, os.path.join(repo, "src", "tpu_jax", "main.py"),
        *sc["extra_args"],
        "--ckpt-path", root, "--seed", "7", "--no-progress",
        "--policy-mode", sc["policy_mode"],
    ]
    for spec in sc["alerts"]:
        cmd += ["--alert", spec]
    for spec in sc["policies"]:
        cmd += ["--policy", spec]
    env = dict(os.environ)
    env.update(sc["env"])
    timed_out = False
    proc = subprocess.Popen(
        cmd, cwd=repo, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # chaos driver "kill_replica": watch the fleet's handshake files
    # until every process replica reports ready, give the load shape a
    # moment to start flowing, then SIGKILL replica 0's worker — rid 0
    # because LIFO scale-down drains the HIGHEST rid, so an autoscaler
    # riding along can never have politely drained our victim first.
    kill_info = {"kills": 0}
    if sc.get("driver") == "kill_replica":
        import signal
        import threading

        xargs = list(sc["extra_args"])
        want = (
            int(xargs[xargs.index("--serve-replicas") + 1])
            if "--serve-replicas" in xargs
            else 1
        )

        def _kill_driver():
            fleet = os.path.join(root, "serve-fleet")
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline and proc.poll() is None:
                ready = {}
                for fn in sorted(os.listdir(fleet)) if os.path.isdir(
                    fleet
                ) else []:
                    if (
                        not fn.startswith("replica-")
                        or not fn.endswith(".json")
                        or ".spec." in fn
                    ):
                        continue
                    try:
                        with open(os.path.join(fleet, fn)) as fh:
                            hs = json.load(fh)
                    except (OSError, ValueError):
                        continue  # mid-write handshake; next poll has it
                    if hs.get("state") == "ready" and hs.get("pid"):
                        ready[fn] = int(hs["pid"])
                if len(ready) >= want:
                    time.sleep(2.0)
                    try:
                        os.kill(ready[min(ready)], signal.SIGKILL)
                        kill_info["kills"] += 1
                    except OSError:
                        pass
                    return
                time.sleep(0.25)

        threading.Thread(target=_kill_driver, daemon=True).start()
    try:
        out, err = proc.communicate(timeout=900)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()
        out, err = proc.communicate()

    events, _files = run_report.load_run(root)
    policy_states: dict[str, int] = {}
    recompiles = 0
    restarts = 0
    failed_requests = None
    phases = None
    for ev in events:
        kind = ev.get("kind")
        p = ev.get("payload") or {}
        if kind == "policy":
            st = p.get("state", "?")
            policy_states[st] = policy_states.get(st, 0) + 1
        elif kind == "compile" and p.get("recompile_after_warmup"):
            recompiles += 1
        elif kind == "replica" and (
            p.get("lifecycle") == "attempt_start" and p.get("attempt")
        ):
            # attempt >= 1 on a replica lifecycle event IS a supervisor
            # restart (attempt 0 is the original launch)
            restarts += 1
        elif kind == "serve":
            if p.get("phases"):
                phases = p["phases"]
            if p.get("failed") is not None:
                failed_requests = p["failed"]
    # recovery is judged against the WORST phase (the storm may land a
    # burst early under Poisson arrivals): the final phase's p99 must sit
    # below the cliff, wherever the cliff was — and the after phase must
    # have actually COMPLETED requests (an empty phase's p99 is 0.0,
    # which would read a total post-flash outage as "recovered")
    p99_recovered = False
    if phases and all(k in phases for k in ("before", "flash", "after")):
        after = phases["after"]["latency_ms"]["p99"]
        worst = max(
            phases[k]["latency_ms"]["p99"] for k in ("before", "flash")
        )
        p99_recovered = bool(
            phases["after"].get("n", 0) > 0
            and after > 0
            and after < worst
        )
    observed = {
        "final_rc": proc.returncode,
        "resizes": 0,
        "rollbacks": 0,
        "alerts_fired": sum(
            1 for ev in events
            if ev.get("kind") == "alert"
            and (ev.get("payload") or {}).get("state") == "firing"
        ),
        "restarts": restarts, "preemptions": 0,
        "kills": kill_info["kills"],
        "failed_requests": failed_requests,
        "policy_requested": policy_states.get("requested", 0),
        "policy_completed": policy_states.get("completed", 0),
        "policy_failed": policy_states.get("failed", 0),
        "policy_dry_run": policy_states.get("dry_run", 0),
        "policy_cooldown": policy_states.get("cooldown", 0),
        "policy_budget": policy_states.get("budget", 0),
        "policy_pending": len(pending_actions(events)),
        "crash_dump_evidence": False,
        "goodput_frac": None,
        "recompiles": recompiles,
        "p99_recovered": p99_recovered,
        "phases": phases,
    }
    problems = check_chaos_expectations(sc["expect"], observed)
    if timed_out:
        problems.append("scenario timed out after 900s (process killed)")
    if observed["policy_pending"]:
        problems.append(
            f"{observed['policy_pending']} policy action(s) still "
            "pending (requested, never completed)"
        )
    check_rc = events_check_rc(root, require_kinds=tuple(sc["require_kinds"]))
    if check_rc != 0:
        problems.append(f"events_check_rc={check_rc}")
    row = {
        "desc": sc["desc"],
        "fault_plan": sc["fault_plan"],
        "alerts": list(sc["alerts"]),
        "policies": list(sc["policies"]),
        "policy_mode": sc["policy_mode"],
        "driver": [sc["driver"]] if sc.get("driver") else [],
        **observed,
        "events_check_rc": check_rc,
        "green": not problems,
        "problems": problems,
        "stderr_tail": (err or "")[-400:] if problems else "",
    }
    return row, problems, check_rc


def bench_chaos(out_path: str = "CHAOS.json", scenarios=None) -> dict:
    """The chaos gauntlet (ISSUE 13): run every named scenario of
    ``resilience.faults.CHAOS_SCENARIOS`` — preempt x straggler-stall x
    corrupt-shard (nan_grad) x host-flap, alone and composed — end-to-end
    under the fleet supervisor with the closed-loop policy engine active,
    and commit the scoreboard as ``CHAOS.json`` the way GOODPUT.json
    prices the kill->shrink->readmit->expand run.

    Every scenario must recover via policy/supervisor actions alone: no
    operator marker files (the only marker a driver writes is
    ``host-1.up`` — the SCHEDULER's re-admission interface, exactly as in
    the GOODPUT gauntlet).  Each run self-validates its event stream
    (``run_report --check`` plus the scenario's required kinds — the
    policy scenarios require ``policy``), its expectations are checked by
    ``check_chaos_expectations`` (a violated scenario fails the leg), and
    no policy action may end the gauntlet still pending
    (``run_report --policy`` semantics).

    CPU emulation caveat (same as the resilience leg): rank 1 is the
    pid+event-file host emulation from ``tests/fleet_pool_worker.py`` —
    the pinned CI jax cannot run multi-process collectives on the CPU
    backend — and the persistent straggler is that rank reporting a
    slowed ``step/dispatch_s`` sketch (``EMU_SLOW_DISPATCH_ENV``), which
    is exactly the interface a genuinely slow host presents to the
    supervisor-side alert engine.
    """
    import os
    import subprocess
    import sys
    import tempfile
    import threading

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.resilience import (
        CHAOS_KIND,
        CHAOS_SCENARIOS,
        check_chaos_expectations,
    )
    from distributed_training_comparison_tpu.ops.policy import pending_actions
    from distributed_training_comparison_tpu.resilience.control import (
        unapplied_actions,
    )

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import run_report

    platform = _require_explicit_cpu("--chaos")
    repo = os.path.dirname(os.path.abspath(__file__))
    child = os.path.join(repo, "tests", "fleet_pool_worker.py")
    names = list(scenarios or CHAOS_SCENARIOS)
    rows: dict[str, dict] = {}
    failures: list[str] = []
    worst_rc = 0

    for name in names:
        sc = CHAOS_SCENARIOS[name]
        if sc.get("session") == "serve":
            # the flash-crowd x serve axis: the real --serve entry, not
            # the training fleet worker (see _run_serve_chaos_scenario)
            row, problems, check_rc = _run_serve_chaos_scenario(
                name, sc, repo, run_report
            )
            worst_rc = max(worst_rc, check_rc)
            rows[name] = row
            emit_progress(f"chaos/{name}", {
                "rc": row["final_rc"], "green": row["green"],
                "problems": problems,
                "recompiles": row["recompiles"],
                "p99_recovered": row["p99_recovered"],
            })
            if problems:
                failures.append(
                    f"{name}: {problems} (stderr tail: "
                    f"{row.get('stderr_tail', '')})"
                )
            continue
        root = tempfile.mkdtemp(prefix=f"chaos-{name}-")
        goodput_json = os.path.join(root, "goodput-scenario.json")
        cmd = [
            sys.executable, child, "--supervise",
            "--fleet-hosts", "2", "--fleet-local-devices", "1",
            "--fleet-grace-secs", "3", "--fleet-poll-secs", "0.2",
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "10",
            "--no-progress", "--eval-step", "1000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "2", "--heartbeat-secs", "0.2",
            "--ckpt-path", root, "--goodput-json", goodput_json,
            "--policy-mode", sc["policy_mode"],
        ]
        if sc["fault_plan"]:
            cmd += ["--fault-plan", sc["fault_plan"]]
        for spec in sc["alerts"]:
            cmd += ["--alert", spec]
        for spec in sc["policies"]:
            cmd += ["--policy", spec]
        # {root} in extra_args resolves to the scenario's ckpt root
        # ({host} survives untouched for the SchedulerProbe itself)
        cmd += [a.replace("{root}", root) for a in sc["extra_args"]]
        env = dict(os.environ)
        env.update(sc["env"])

        driver_log: list = []

        def drive(proc, script=sc["driver"]) -> None:
            # the external environment only: spot reclaim (SIGKILL) and
            # the scheduler's re-admission signal — never an operator
            # action (no host-i.down is ever written here; the probe
            # variant writes no marker at all)
            if script is not None:
                _drive_fleet_gauntlet(
                    root, proc, driver_log,
                    readmit=(
                        "probe" if script == "probe_readmit_host1"
                        else script == "kill_and_readmit_host1"
                    ),
                )

        proc = subprocess.Popen(
            cmd, cwd=repo, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            # own process group: a timeout kill must take the supervised
            # fleet's rank children down too, not orphan them onto the
            # next scenario's timings
            start_new_session=True,
        )
        driver = threading.Thread(target=drive, args=(proc,), daemon=True)
        driver.start()
        timed_out = False
        try:
            out, err = proc.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            # a wedged scenario must neither leak its process tree nor
            # abort the gauntlet: kill the whole group, record a red
            # row, move on
            timed_out = True
            import signal as _signal

            try:
                os.killpg(proc.pid, _signal.SIGKILL)
            except (OSError, ProcessLookupError):
                proc.kill()
            out, err = proc.communicate()
            driver_log.append("scenario timed out after 900s; killed")
        driver.join(timeout=10.0)

        events, _files = run_report.load_run(root)
        by_kind: dict[str, int] = {}
        for ev in events:
            by_kind[ev.get("kind", "?")] = by_kind.get(ev.get("kind", "?"), 0) + 1
        policy_states: dict[str, int] = {}
        for ev in events:
            if ev.get("kind") == "policy":
                st = (ev.get("payload") or {}).get("state", "?")
                policy_states[st] = policy_states.get(st, 0) + 1
        # the decide->apply trail: every control request's end state,
        # split by whether the application landed INSIDE an epoch (the
        # tentpole's chunk boundary) or at the legacy epoch boundary
        controls_applied = control_mid_epoch = controls_superseded = 0
        control_ttms: list[float] = []
        for ev in events:
            if ev.get("kind") != "control":
                continue
            p = ev.get("payload") or {}
            if p.get("state") == "applied":
                controls_applied += 1
                if p.get("mid_epoch"):
                    control_mid_epoch += 1
                if isinstance(p.get("ttm_s"), (int, float)):
                    control_ttms.append(float(p["ttm_s"]))
            elif p.get("state") == "superseded":
                controls_superseded += 1
        try:
            with open(goodput_json) as f:
                gp = json.load(f)
        except (OSError, ValueError):
            gp = {}
        evidence_ok = False
        for dump in sorted(Path(root).glob("version-*/crash_dump*.json")):
            try:
                d = json.loads(dump.read_text())
            except (OSError, ValueError):
                continue
            ev_block = d.get("evidence") or {}
            if ev_block.get("alert_timeline") and ev_block.get("policy_timeline"):
                evidence_ok = True
        observed = {
            "final_rc": proc.returncode,
            "resizes": by_kind.get("resize", 0),
            "rollbacks": by_kind.get("rollback", 0),
            "alerts_fired": sum(
                1 for ev in events
                if ev.get("kind") == "alert"
                and (ev.get("payload") or {}).get("state") == "firing"
            ),
            "restarts": int(gp.get("restarts", 0) or 0),
            "preemptions": int(gp.get("preemptions", 0) or 0),
            "policy_requested": policy_states.get("requested", 0),
            "policy_completed": policy_states.get("completed", 0),
            "policy_failed": policy_states.get("failed", 0),
            "policy_dry_run": policy_states.get("dry_run", 0),
            "policy_cooldown": policy_states.get("cooldown", 0),
            "policy_budget": policy_states.get("budget", 0),
            "policy_pending": len(pending_actions(events)),
            "controls_applied": controls_applied,
            "control_mid_epoch": control_mid_epoch,
            "controls_superseded": controls_superseded,
            "control_ttm_max_s": round(max(control_ttms), 3)
            if control_ttms else None,
            "crash_dump_evidence": evidence_ok,
            "goodput_frac": gp.get("goodput_frac"),
        }
        problems = check_chaos_expectations(sc["expect"], observed)
        if timed_out:
            problems.append("scenario timed out after 900s (process killed)")
        if observed["policy_pending"]:
            problems.append(
                f"{observed['policy_pending']} policy action(s) still "
                "pending (requested, never completed)"
            )
        never_applied = unapplied_actions(events)
        if never_applied:
            problems.append(
                f"{len(never_applied)} acted decision(s) completed with "
                "no 'applied' control event (decide->apply trail broken)"
            )
        check_rc = events_check_rc(
            root, require_kinds=tuple(sc["require_kinds"])
        )
        worst_rc = max(worst_rc, check_rc)
        if check_rc != 0:
            problems.append(f"events_check_rc={check_rc}")
        row = {
            "desc": sc["desc"],
            "fault_plan": sc["fault_plan"],
            "alerts": list(sc["alerts"]),
            "policies": list(sc["policies"]),
            "policy_mode": sc["policy_mode"],
            "driver": driver_log,
            **observed,
            "events_check_rc": check_rc,
            "green": not problems,
            "problems": problems,
        }
        rows[name] = row
        emit_progress(f"chaos/{name}", {
            "rc": proc.returncode, "green": row["green"],
            "problems": problems, "policy": policy_states,
        })
        if problems:
            failures.append(
                f"{name}: {problems} (stderr tail: {(err or '')[-800:]})"
            )
        # one `chaos` event per scenario on a bus bound to the scenario
        # root, so the scoreboard row itself is replayable from the stream
        chaos_bus = obs.EventBus(run_id=obs.new_run_id())
        chaos_bus.bind_dir(root)
        chaos_bus.emit(
            CHAOS_KIND, scenario=name, green=row["green"],
            policy_completed=observed["policy_completed"],
            resizes=observed["resizes"], rollbacks=observed["rollbacks"],
            final_rc=observed["final_rc"],
        )
        chaos_bus.close()

    record = {
        "metric": "chaos_matrix",
        "platform": platform,
        "scenarios": rows,
        "green": not failures,
        "events_check_rc": worst_rc,
        "note": (
            "CPU capture: rank 1 is the pid+event-file host emulation "
            "(tests/fleet_pool_worker.py) and the persistent straggler is "
            "its slowed step/dispatch_s sketch; every supervisor/policy "
            "code path (alert evaluation, drain markers, request channel, "
            "world re-render) runs for real. Recovery is policy/supervisor"
            "-driven only — the single driver-written marker is host-1.up, "
            "the scheduler's re-admission interface."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "metric": "chaos_matrix",
        "green": record["green"],
        "scenarios": {
            n: {
                "green": r["green"], "final_rc": r["final_rc"],
                "policy_completed": r["policy_completed"],
                "resizes": r["resizes"], "rollbacks": r["rollbacks"],
                "goodput_frac": r["goodput_frac"],
            }
            for n, r in rows.items()
        },
        "full_record": out_path,
    }))
    if failures:
        raise RuntimeError(
            "chaos gauntlet red: " + "; ".join(failures)
        )
    return record


def bench_control(out_path: str = "BENCH_CONTROL.json") -> dict:
    """The mid-epoch control-plane leg (the tentpole's scoreboard): the
    SAME policy rollback decision applied through both boundaries —
    ``--control-boundary chunk`` (the new control channel, applied at
    the next chunk boundary inside the epoch) vs ``epoch`` (the legacy
    request channel, applied at the next epoch boundary) — plus a
    supervised fleet leg whose ``drain_host`` decision rides the control
    channel into a clean mid-epoch drain-checkpoint.  The committed
    record prices time-to-mitigation per decision: ``ttm_s`` (decide →
    apply wall seconds) and ``steps_since_decide`` (the step distance),
    with the gate that every CHUNK-boundary application landed within
    one chunk of its decision — the whole point of the boundary move.

    Sizing: 512 synthetic examples / batch 32 = 16 steps per epoch with
    ``--device-chunk-steps 2`` — eight poll boundaries per epoch, so the
    epoch-boundary baseline is measurably (≈8x in steps) slower to
    mitigate than the chunk path on identical decisions.
    """
    import os
    import subprocess
    import sys
    import tempfile

    platform = _require_explicit_cpu("--control")
    repo = os.path.dirname(os.path.abspath(__file__))
    child = os.path.join(repo, "tests", "fleet_pool_worker.py")
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    CHUNK = 2
    base = [
        "--synthetic-data", "--limit-examples", "512",
        "--batch-size", "32", "--no-progress", "--eval-step", "1000",
        "--save-last-min-secs", "0", "--seed", "7",
        "--device-chunk-steps", str(CHUNK), "--heartbeat-secs", "0.2",
    ]
    # a loss spike injected mid-epoch 2 — AFTER the epoch-0/1 verified
    # saves, so the rollback decision has a target and is eligible for
    # the chunk boundary (a decision that precedes the first save is
    # deliberately deferred to the epoch boundary; that path is covered
    # by the in-process tests, not this scoreboard)
    spike = "train/loss:p95>50:for=1"
    rollback_policy = [
        "--fault-plan", "loss_spike@epoch=2:scale=64:steps=3",
        "--health-spike-mads", "1e9",
        "--alert", spike,
        "--policy", f"{spike} -> rollback:cooldown=9999",
        "--policy-mode", "act",
    ]
    straggler = "step/dispatch_s:p95>30:for=2"
    legs = {
        # in-process engine, one rollback decision, applied at the next
        # CHUNK boundary (mid-epoch) — TTM bounded by one chunk
        "rollback_chunk": {
            "argv": base + rollback_policy
            + ["--epoch", "6", "--control-boundary", "chunk"],
            "supervised": False,
            "expect_boundary": "chunk",
        },
        # the identical decision through the legacy epoch-boundary
        # channel — the baseline the tentpole improves on
        "rollback_epoch": {
            "argv": base + rollback_policy
            + ["--epoch", "6", "--control-boundary", "epoch"],
            "supervised": False,
            "expect_boundary": "epoch",
        },
        # supervised 2-host fleet, persistent straggler: the drain_host
        # decision writes control-drain.req and the trainer exits
        # through the proven mid-epoch drain-checkpoint at its next
        # chunk instead of riding out the SIGTERM grace race
        "drain_fleet": {
            "argv": base + [
                "--supervise", "--fleet-hosts", "2",
                "--fleet-local-devices", "1", "--fleet-grace-secs", "3",
                "--fleet-poll-secs", "0.2", "--epoch", "10",
                "--alert", straggler,
                "--policy", f"{straggler} -> drain_host:cooldown=120",
                "--policy-mode", "act",
            ],
            "supervised": True,
            "expect_boundary": None,  # chunk OR the epoch's final chunk
        },
    }

    rows: dict[str, dict] = {}
    failures: list[str] = []
    worst_rc = 0
    for name, leg in legs.items():
        root = tempfile.mkdtemp(prefix=f"control-{name}-")
        cmd = [sys.executable, child, *leg["argv"], "--ckpt-path", root]
        env = dict(os.environ)
        if leg["supervised"]:
            from distributed_training_comparison_tpu.resilience.faults import (
                EMU_SLOW_DISPATCH_ENV,
            )

            env[EMU_SLOW_DISPATCH_ENV] = "60"
        proc = subprocess.run(
            cmd, cwd=repo, env=env, capture_output=True, text=True,
            timeout=900,
        )
        events, _files = run_report.load_run(root)
        applied = [
            (ev.get("payload") or {})
            for ev in events
            if ev.get("kind") == "control"
            and (ev.get("payload") or {}).get("state") == "applied"
        ]
        check_rc = events_check_rc(root, require_kinds=("policy", "control"))
        worst_rc = max(worst_rc, check_rc)
        row = {
            "final_rc": proc.returncode,
            "controls_applied": len(applied),
            "applications": [
                {
                    "action": p.get("action"),
                    "verb": p.get("verb"),
                    "boundary": p.get("boundary"),
                    "mid_epoch": p.get("mid_epoch"),
                    "ttm_s": p.get("ttm_s"),
                    "steps_since_decide": p.get("steps_since_decide"),
                }
                for p in applied
            ],
            "events_check_rc": check_rc,
        }
        problems: list[str] = []
        if proc.returncode != 0:
            problems.append(f"final_rc={proc.returncode}")
        if not applied:
            problems.append("no applied control event")
        if check_rc != 0:
            problems.append(f"events_check_rc={check_rc}")
        want = leg["expect_boundary"]
        if want is not None and any(
            p.get("boundary") != want for p in applied
        ):
            problems.append(
                f"boundary mismatch (wanted {want}): "
                f"{[p.get('boundary') for p in applied]}"
            )
        # THE gate: a chunk-boundary application must land within one
        # chunk of its decision's step position
        for p in applied:
            ssd = p.get("steps_since_decide")
            if p.get("boundary") == "chunk" and isinstance(ssd, int) \
                    and ssd > CHUNK:
                problems.append(
                    f"chunk-boundary apply took {ssd} steps (> one "
                    f"{CHUNK}-step chunk)"
                )
        row["green"] = not problems
        row["problems"] = problems
        rows[name] = row
        emit_progress(f"control/{name}", {
            "rc": proc.returncode, "green": row["green"],
            "applications": row["applications"], "problems": problems,
        })
        if problems:
            failures.append(
                f"{name}: {problems} (stderr tail: "
                f"{(proc.stderr or '')[-800:]})"
            )

    # the headline: identical decision, steps-to-mitigation both ways
    def _ssd(name):
        apps = rows[name]["applications"]
        return apps[0]["steps_since_decide"] if apps else None

    record = {
        "metric": "control_ttm",
        "platform": platform,
        "chunk_steps": CHUNK,
        "steps_per_epoch": 16,
        "legs": rows,
        "steps_to_mitigation": {
            "chunk": _ssd("rollback_chunk"),
            "epoch": _ssd("rollback_epoch"),
        },
        "green": not failures,
        "events_check_rc": worst_rc,
        "note": (
            "Identical spike-triggered rollback decision applied through "
            "both boundaries; steps_since_decide counts chunk-boundary "
            "marks between the decision and its application. The fleet "
            "leg's drain_host rides control-drain.req into a clean "
            "mid-epoch drain-checkpoint (CPU capture: rank 1 is the "
            "fleet_pool_worker host emulation)."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "metric": "control_ttm",
        "green": record["green"],
        "steps_to_mitigation": record["steps_to_mitigation"],
        "full_record": out_path,
    }))
    if failures:
        raise RuntimeError("control leg red: " + "; ".join(failures))
    return record


def bench_health(
    out_path: str = "HEALTH.json",
    trainer_model=None,
    extra_argv: tuple = (),
) -> dict:
    """The training-health leg: one run through the seeded detector gauntlet
    — ``nan_grad`` at epoch 1 (non-finite steps skipped by the compiled
    guard, then rolled back), ``loss_spike`` at epoch 2 (finite spikes
    caught by the median/MAD window, rolled back) — committed as
    ``HEALTH.json`` (pretty-print with ``tools/health_report.py``).

    In-process on purpose (unlike the resilience leg's subprocess
    supervisor): watchdog rollback is an *in-run* recovery, so the leg
    measures exactly what production pays — the wasted epoch moves from
    goodput's ``step`` phase to ``rollback``, and the final report carries
    both the health counters and the goodput split including that waste.
    ``trainer_model``/``extra_argv`` let the slow-test harness swap in a
    tiny model and smaller sizing.
    """
    import tempfile
    from pathlib import Path

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.health import write_health
    from distributed_training_comparison_tpu.resilience.goodput import (
        aggregate_goodput,
        load_goodput_records,
    )
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    platform = jax.devices()[0].platform
    ckpt_root = tempfile.mkdtemp(prefix="health-bench-")
    if platform == "cpu":
        # CI smoke sizing: a single-core resnet18 EPOCH-runner compile alone
        # costs ~3 min (same constraint bench_resilience sized around), so
        # the leg runs 3-step epochs and arms the detectors for that scale
        # (window/baseline 6, rollback at 2 consecutive bad steps, the
        # spike window covering a whole epoch)
        size_args = [
            "--limit-examples", "128", "--batch-size", "32", "--epoch", "4",
            "--health-window", "6", "--health-bad-steps", "2",
        ]
        fault_plan = "nan_grad@epoch=1;loss_spike@epoch=2:step=0:steps=3"
    else:
        size_args = ["--limit-examples", "4096", "--batch-size", "256", "--epoch", "6"]
        fault_plan = "nan_grad@epoch=1;loss_spike@epoch=2"
    hp = load_config(
        "tpu",
        [
            "--synthetic-data", *size_args,
            "--ckpt-path", ckpt_root,
            "--save-last-min-secs", "0", "--no-progress",
            "--seed", "7",
            "--fault-plan", fault_plan,
            *extra_argv,
        ],
    )
    trainer = Trainer(hp, model=trainer_model)
    try:
        trainer.fit()
        summary = trainer.watchdog.summary()
    finally:
        trainer.close()
    records = load_goodput_records(
        Path(ckpt_root) / "version-0" / "goodput.jsonl"
    )
    goodput = aggregate_goodput(records)
    record = {
        **summary,
        "platform": platform,
        "fault_plan": hp.fault_plan,
        "events_check_rc": events_check_rc(ckpt_root),
        "goodput": {
            "goodput_frac": goodput["goodput_frac"],
            "productive_s": goodput["productive_s"],
            "rollback_s": goodput["phase_totals_s"]["rollback"],
            "total_wall_s": goodput["total_wall_s"],
        },
    }
    write_health(out_path, record)
    print(json.dumps({
        "metric": record["metric"],
        "skipped_steps": record["skipped_steps"],
        "spike_steps": record["spike_steps"],
        "rollbacks": record["rollbacks"],
        "desyncs": record["desyncs"],
        "rollback_s": record["goodput"]["rollback_s"],
        "goodput_frac": record["goodput"]["goodput_frac"],
        "platform": platform,
        "full_record": out_path,
    }))
    return record


def bench_obs_overhead(
    out_path: str = "BENCH_OBS.json",
    steps: int = 50_000,
    budget_us_per_step: float = 25.0,
) -> dict:
    """The telemetry-overhead leg: what one trained step PAYS for the
    per-step metrics pipeline — committed as ``BENCH_OBS.json``.

    The deal obs/metrics.py offers the trainer is "record every step,
    bounded bus traffic"; this leg prices the record side.  Two identical
    loops run the trainer's per-step accounting shape — per chunk: three
    ``StepTimeMeter`` phase intervals, ``note_steps`` + the heartbeat's
    cadence check + ``maybe_flush`` (with the resource gauges sampled on
    flush-due windows) against a real bound bus with the mmap flight ring
    attached; per epoch: one vectorized ``record_many`` pass for the
    stacked grad_norm/loss arrays — once with the registry wired and once
    with telemetry off (``metrics=None``, no bus).  The difference per
    step must stay under ``budget_us_per_step`` (microseconds — the
    stated budget; a CIFAR step is ~10ms on one TPU core, so 25µs is
    <0.3%).  A second leg reprices the same machinery *inside a real
    training run* (tiny conv net, heartbeats at 1s, a live
    ``--metrics-port`` exporter scraped mid-fit) — informational on a CPU
    container, where run-to-run step-time noise is orders of magnitude
    above the budget (see the committed record's ``note``); the budget
    verdict stays on the synthetic leg.  The capture self-validates: the
    flush events the measured loops emitted are schema-checked by
    ``run_report --check`` (``events_check_rc``), and ``within_budget``
    records the verdict the slow-marked test asserts.
    """
    import tempfile
    from pathlib import Path

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.utils import StepTimeMeter

    chunk = 32          # steps per simulated chunk dispatch
    epoch_len = 512     # steps per simulated epoch (one record_many pass)
    rng = np.random.default_rng(0)
    grad_norms = rng.lognormal(0.0, 0.5, epoch_len)
    losses = rng.normal(4.0, 0.3, epoch_len)

    ckpt_root = tempfile.mkdtemp(prefix="obs-bench-")

    def run_loop(with_obs: bool) -> tuple[float, int]:
        import urllib.request

        obs.reset()
        bus = obs.configure(run_id=obs.new_run_id(), persist=with_obs)
        flushes = 0
        exporter = None
        if with_obs:
            bus.bind_dir(ckpt_root)
            bus.attach_ring(Path(ckpt_root) / obs.ring_filename())
            registry = obs.MetricRegistry(flush_steps=50)
            heartbeat = obs.HeartbeatEmitter(bus, every_s=10.0)
            resources = obs.ResourceSampler(ckpt_root=ckpt_root)
            # the live endpoint idles on its thread for the whole measured
            # loop and serves ONE scrape mid-loop, so within_budget prices
            # the exporter too, not just the record path
            exporter = obs.MetricsExporter(port=0, registry=registry).start()
        else:
            registry = None
        meter = StepTimeMeter(metrics=registry)
        scraped = False
        t0 = time.perf_counter()
        done = 0
        while done < steps:
            take = min(chunk, steps - done)
            # the three phase intervals every chunk dispatch records
            meter.add("h2d_wait", 1e-6)
            meter.add("dispatch", 1e-6)
            meter.add("compute", 1e-6)
            meter.note_chunk()
            done += take
            if registry is not None:
                registry.note_steps(take)
                # the trainer's _obs_tick shape: cadence-checked heartbeat,
                # resource gauges only on flush-due windows, then the flush
                heartbeat.beat(epoch=0, step=done, flush_seq=registry.flushes)
                if registry.flush_due():
                    resources.sample(registry)
                    registry.maybe_flush(bus, epoch=0, step=done)
            if done % epoch_len == 0 and registry is not None:
                # the per-epoch stacked-array pass (vectorized, not per-step)
                registry.histogram("train/grad_norm").record_many(grad_norms)
                registry.histogram("train/loss").record_many(losses)
                registry.flush(bus, epoch=done // epoch_len)
            if not scraped and done >= steps // 2 and exporter is not None:
                scraped = True
                urllib.request.urlopen(
                    f"http://127.0.0.1:{exporter.port}/metrics", timeout=5
                ).read()
        elapsed = time.perf_counter() - t0
        if registry is not None:
            flushes = registry.flushes
        if exporter is not None:
            exporter.close()
        obs.reset()
        return elapsed, flushes

    run_loop(True)  # warmup (file creation, first-touch of the ring pages)
    with_t, flushes = run_loop(True)
    without_t, _ = run_loop(False)
    overhead_us = (with_t - without_t) / steps * 1e6
    compile_leg = _bench_obs_compile_leg(ckpt_root, budget_us_per_step)
    real = _bench_obs_real_step(Path(ckpt_root))
    record = {
        "metric": "obs_overhead",
        "steps": steps,
        "chunk": chunk,
        "flushes": flushes,
        "with_obs_s": round(with_t, 4),
        "without_obs_s": round(without_t, 4),
        "overhead_us_per_step": round(overhead_us, 3),
        "budget_us_per_step": budget_us_per_step,
        "within_budget": bool(overhead_us < budget_us_per_step),
        "compile_capture": compile_leg,
        "real_step": real,
        # the compile leg's observed compile must be ON the stream — a
        # capture without it means the hook silently degraded
        "events_check_rc": events_check_rc(
            ckpt_root, require_kinds=("compile",)
        ),
        "platform": jax.devices()[0].platform,
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({k: record[k] for k in (
        "metric", "steps", "flushes", "overhead_us_per_step",
        "budget_us_per_step", "within_budget", "events_check_rc", "platform",
    )} | {
        "compile_capture_us_per_step": compile_leg.get("overhead_us_per_step"),
        "compile_capture_within_budget": compile_leg.get("within_budget"),
        "real_step_overhead_us": real.get("overhead_us_per_step"),
        "scrape_ok": real.get("scrape_ok"),
        "full_record": out_path,
    }))
    return record


def _bench_obs_compile_leg(
    ckpt_root, budget_us_per_step: float, dispatches: int = 2000,
    chunk: int = 32, leaves: int = 128,
) -> dict:
    """Price the compile-capture hook's DISPATCH side: what every chunk
    dispatch pays for riding the instrumented path instead of calling the
    jitted function directly (obs/compilation.py).

    The compile itself happens once per executable and is not a per-step
    cost; the recurring price is the wrapper's signature key (one pytree
    flatten + a (shape, dtype) tuple over a ``leaves``-leaf state — the
    realistic shape of a train-state arg) plus the per-executable
    dispatch-histogram record.  Two identical loops dispatch the same
    tiny tree-map program ``dispatches`` times, instrumented vs plain
    jit; the delta per dispatch, divided by the chunk length a dispatch
    amortizes over, is the per-trained-step price judged against the
    same 25 µs budget as the record path.  The observed compile lands on
    the bound bus, so the capture's event stream carries a ``compile``
    event for the self-check to require."""
    import jax.numpy as jnp

    from distributed_training_comparison_tpu import obs

    tree = {f"w{i}": jnp.zeros((4, 4), jnp.float32) for i in range(leaves)}
    fn = jax.jit(
        lambda t: jax.tree_util.tree_map(lambda x: x + 1.0, t)
    )

    obs.reset()
    bus = obs.configure(run_id=obs.new_run_id())
    bus.bind_dir(ckpt_root)
    registry = obs.MetricRegistry(flush_steps=10 ** 9)
    monitor = obs.CompileMonitor(bus=bus, registry=registry)
    inst = monitor.instrument(fn, "bench_state_update")

    def loop(call) -> float:
        t = call(tree)  # warm: compile (observed once on the inst path)
        t0 = time.perf_counter()
        for _ in range(dispatches):
            t = call(t)
        jax.block_until_ready(t)
        return time.perf_counter() - t0

    without_t = loop(fn)
    with_t = loop(inst)
    registry.flush(bus)
    ledger = monitor.ledger()
    obs.reset(bus)
    per_dispatch_us = (with_t - without_t) / dispatches * 1e6
    per_step_us = per_dispatch_us / chunk
    return {
        "dispatches": dispatches,
        "state_leaves": leaves,
        "chunk": chunk,
        "with_monitor_s": round(with_t, 4),
        "without_monitor_s": round(without_t, 4),
        "overhead_us_per_dispatch": round(per_dispatch_us, 3),
        "overhead_us_per_step": round(per_step_us, 3),
        "budget_us_per_step": budget_us_per_step,
        "within_budget": bool(per_step_us < budget_us_per_step),
        "observed_compiles": sum(r["compiles"] for r in ledger),
        "compile_s": round(sum(r["compile_s"] for r in ledger), 4),
    }


def _bench_obs_real_step(ckpt_root) -> dict:
    """Price record + heartbeat + one live exporter scrape INSIDE a real
    training step: the same tiny-net trainer the e2e tests drive, run
    once with the full live-operations plane (metrics + 1s heartbeats +
    mmap ring + an OpenMetrics endpoint scraped mid-fit) and once with
    ``--no-obs``; the per-step delta is the measured price.  On the CPU
    container this number is DOMINATED by run-to-run jitter (a CPU
    trainer step is ~ms with >10% variance — hundreds of µs — against a
    25µs budget), so the committed record carries it as informational
    with a caveat; recapture on a real TPU host for a binding number.
    """
    import threading
    import urllib.request

    import flax.linen as lnn

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer

    class BenchNet(lnn.Module):
        """Same shape as the e2e tests' TinyNet: conv+BN+dense."""

        num_classes: int = 100

        @lnn.compact
        def __call__(self, x, train: bool = False):
            x = lnn.Conv(8, (3, 3), strides=2, use_bias=False)(x)
            x = lnn.BatchNorm(use_running_average=not train)(x)
            x = lnn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            return lnn.Dense(self.num_classes)(x)

    epochs, steps_per_epoch = 4, 18  # 640-example synthetic split @ bs 32

    def run(with_obs: bool, tag: str) -> tuple[float, dict]:
        obs.reset()
        argv = [
            "--synthetic-data", "--limit-examples", "640",
            "--batch-size", "32", "--epoch", str(epochs),
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "6",  # chunk boundaries = beat points
            "--ckpt-path", str(ckpt_root / f"real-{tag}"),
        ]
        if with_obs:
            argv += [
                "--metrics-flush-steps", "8", "--heartbeat-secs", "1",
                "--metrics-port", "0",  # flag 0 = off; bench binds its own
            ]
        else:
            argv += ["--no-obs", "--no-flight-ring"]
        hp = load_config("tpu", argv)
        trainer = Trainer(hp, model=BenchNet())
        scrape: dict = {}
        if with_obs:
            # the live endpoint, on an ephemeral port, scraped while fit()
            # runs — the scrape itself is part of what this leg prices
            trainer.exporter = obs.MetricsExporter(
                port=0, registry=trainer.metrics,
                heartbeats=trainer.heartbeat,
            ).start()

            def scraper():
                # retry until the exposition carries real metric families
                # (an empty pre-training scrape is just "# EOF")
                url = f"http://127.0.0.1:{trainer.exporter.port}/metrics"
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    time.sleep(0.1)
                    try:
                        with urllib.request.urlopen(url, timeout=2) as r:
                            body = r.read()
                    except OSError:
                        continue
                    if b"dtc_train_loss" in body:
                        scrape.update(ok=True, bytes=len(body))
                        return
                scrape.update(ok=False)

            threading.Thread(target=scraper, daemon=True).start()
        t0 = time.perf_counter()
        try:
            trainer.fit()
        finally:
            elapsed = time.perf_counter() - t0
            if with_obs:
                scrape.setdefault("ok", False)
                scrape["heartbeats"] = trainer.heartbeat.emitted
            trainer.close()
        obs.reset()
        return elapsed, scrape

    run(True, "warmup")  # compile + file-creation warmup for both legs
    with_t, scrape = run(True, "on")
    without_t, _ = run(False, "off")
    steps = epochs * steps_per_epoch
    return {
        "steps": steps,
        "with_obs_s": round(with_t, 4),
        "without_obs_s": round(without_t, 4),
        "overhead_us_per_step": round((with_t - without_t) / steps * 1e6, 1),
        "scrape_ok": bool(scrape.get("ok")),
        "scrape_bytes": scrape.get("bytes", 0),
        "heartbeats": scrape.get("heartbeats", 0),
        "note": (
            "informational on CPU: per-step jitter of a CPU trainer run "
            "(~ms steps, eval + checkpoint in the loop) is far above the "
            "25us budget; the budget verdict is the synthetic leg's. "
            "Recapture on a TPU host for a binding in-step price."
        ),
    }


def _bench_comms_child(argv) -> None:
    """One bench-comms leg, run in a FRESH process: the parent forces the
    virtual device count (``forced_host_device_env``) before jax
    initializes here, so the leg gets a real N-way data axis on the CPU
    container.  Trains a tiny conv+BN+MLP net through the full Trainer
    stack (device data mode, chunked dispatches, obs on) so the committed
    numbers come from the SAME compile events / metric sketches a
    production run emits — argv: ``CKPT_DIR [trainer flags...]``."""
    import flax.linen as lnn

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer

    ckpt_dir, extra = argv[0], list(argv[1:])

    class CommsNet(lnn.Module):
        """Tiny but momentum-visible: the 256-wide MLP keeps the optimizer
        state a measurable slice of the update executable's arguments."""

        num_classes: int = 100

        @lnn.compact
        def __call__(self, x, train: bool = False):
            x = lnn.Conv(16, (3, 3), strides=2, use_bias=False)(x)
            x = lnn.BatchNorm(use_running_average=not train)(x)
            x = lnn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            x = lnn.relu(lnn.Dense(256)(x))
            return lnn.Dense(self.num_classes)(x)

    hp = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "512",
            "--batch-size", "32", "--epoch", "3",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "8", "--metrics-flush-steps", "8",
            "--ckpt-path", ckpt_dir,
            *extra,
        ],
    )
    trainer = Trainer(hp, model=CommsNet())
    try:
        trainer.fit()
    finally:
        trainer.close()


def bench_comms(out_path: str = "BENCH_COMMS.json", legs=None) -> dict:
    """The comms leg (ISSUE 11): price the ZeRO-sharded weight update and
    the compressed gradient sync off the compile-event HBM ledger and the
    ``step/dispatch_s`` sketches — the two instruments PR 8 built.

    Five child runs on a forced 4-device data axis (baseline,
    ``--shard-optim``, ``--grad-comms fp16``, ``--grad-comms int8``, and
    the composed ``--shard-optim --grad-comms int8``), each a real Trainer
    run whose event stream self-validates (``run_report --check
    --require-kind compile``).  The committed claims:

    - **ledger**: the train executable's per-device argument+alias+temp
      bytes drop under ``--shard-optim`` by ~the optimizer-state bytes ×
      (1 - 1/N) — the comms/opt_state_bytes* gauges in the same stream
      give the expected saving, the compile events the measured one;
    - **numerics**: per-epoch train loss of every compressed leg against
      the fp32 baseline (the e2e form of the tier-1 pinning tests);
    - **sync term**: total dispatch-span seconds per leg.  On the CPU
      container host==device silicon, so this is informational (the
      quantize work shows, the wire saving doesn't); the numbers that
      bind here are the ledger and the numerics.  Recapture on a TPU pod
      for a binding sync term.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    flags = {
        "base": [],
        "shard_optim": ["--shard-optim"],
        "fp16": ["--grad-comms", "fp16"],
        "int8": ["--grad-comms", "int8"],
        "shard_int8": ["--shard-optim", "--grad-comms", "int8"],
    }
    legs = list(legs or flags)
    if "base" not in legs:
        # every headline column is base-relative; a subset without the
        # baseline would burn minutes of child runs then have nothing to
        # compare against
        legs.insert(0, "base")
    _require_explicit_cpu("--comms")
    env = forced_host_device_env(4)
    results: dict = {}
    worst_rc = 0
    for leg in legs:
        ckpt = tempfile.mkdtemp(prefix=f"comms-bench-{leg}-")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--comms-child", ckpt, *flags[leg]],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"comms bench leg {leg} failed ({proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        rc = events_check_rc(ckpt, require_kinds=("compile",))
        worst_rc = max(worst_rc, rc)
        events, _files = run_report.load_run(ckpt)
        # the train executable's memory row: the largest-argument
        # device-chunk program (the full chunk; the remainder is smaller)
        train_execs = [
            run_report._payload(ev)
            for ev in events
            if ev.get("kind") == "compile"
            and str(run_report._payload(ev).get("name", "")).startswith(
                "device_chunk_runner"
            )
        ]
        exec_row = max(
            train_execs,
            key=lambda p: p.get("argument_bytes", 0) + p.get("alias_bytes", 0),
        )
        update_bytes = sum(
            int(exec_row.get(k, 0))
            for k in ("argument_bytes", "alias_bytes", "temp_bytes")
        )
        merged = run_report.merge_metric_events(
            [e for e in events if e.get("kind") == "metrics"]
        )
        comp = run_report.compute_summary(events)
        losses = [
            run_report._payload(e)["train_loss"]
            for e in events
            if e.get("kind") == "epoch_end"
        ]
        gauge = lambda name: (merged.get(name) or {}).get("value")  # noqa: E731
        results[leg] = {
            "flags": flags[leg],
            "train_exec": {
                k: exec_row.get(k)
                for k in (
                    "name", "argument_bytes", "alias_bytes", "temp_bytes",
                    "output_bytes", "peak_bytes",
                )
            },
            "update_arg_alias_temp_bytes": update_bytes,
            "comms_gauges": {
                k: gauge(f"comms/{k}")
                for k in (
                    "wire_bits", "grad_sync_bytes", "opt_state_bytes",
                    "opt_state_bytes_per_device",
                )
            },
            "dispatch_s": round(comp["totals"]["dispatch_s"], 4),
            "epoch_train_loss": [round(float(l), 6) for l in losses],
            "events_check_rc": rc,
        }

    base = results["base"]
    shard = results.get("shard_optim")
    record: dict = {
        "world": {"devices": 4, "data_axis": 4, "platform": "cpu"},
        "legs": results,
        "events_check_rc": worst_rc,
    }
    if shard:
        opt_total = shard["comms_gauges"]["opt_state_bytes"] or 0
        opt_per_dev = shard["comms_gauges"]["opt_state_bytes_per_device"] or 0
        measured = (
            base["update_arg_alias_temp_bytes"]
            - shard["update_arg_alias_temp_bytes"]
        )
        record["ledger"] = {
            "update_bytes_base": base["update_arg_alias_temp_bytes"],
            "update_bytes_shard_optim": shard["update_arg_alias_temp_bytes"],
            "measured_saving_bytes": measured,
            "expected_opt_state_saving_bytes": opt_total - opt_per_dev,
            "opt_state_shard_ratio": (
                round(opt_per_dev / opt_total, 4) if opt_total else None
            ),
        }
    record["loss_vs_base"] = {
        leg: round(
            max(
                abs(a - b)
                for a, b in zip(
                    results[leg]["epoch_train_loss"],
                    base["epoch_train_loss"],
                )
            ),
            6,
        )
        for leg in legs
        if leg != "base" and results[leg]["epoch_train_loss"]
    }
    record["note"] = (
        "CPU capture: the ledger and loss columns bind (per-device "
        "argument bytes and numerics are silicon-independent); the "
        "dispatch_s sync term is informational — host==device on this "
        "container, so quantize compute shows and wire savings don't. "
        "Recapture on a TPU pod for a binding sync term."
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(
        {
            "key": "comms",
            "ledger": record.get("ledger"),
            "loss_vs_base": record["loss_vs_base"],
            "events_check_rc": worst_rc,
        },
        sort_keys=True,
    ))
    return record


def _bench_parity_child(argv) -> None:
    """One parity-sweep leg in a FRESH process (the parent forces the
    virtual device count before jax initializes here): a real Trainer run
    with ``--parity-check`` on, so the committed verdicts come from the
    SAME capture → replay → eager-diff rail a production debug run uses —
    argv: ``MODEL CKPT_DIR [trainer flags...]`` where MODEL is ``conv``
    (dp/ZeRO/wire legs) or ``vit`` (tp/pp legs — the conv net has no
    model axis to shard)."""
    import flax.linen as lnn

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    model_kind, ckpt_dir, extra = argv[0], argv[1], list(argv[2:])

    class ParityNet(lnn.Module):
        """Same shape family as the comms-bench net: conv+BN (batch_stats
        exercise the relayout stage) + a momentum-visible MLP."""

        num_classes: int = 100

        @lnn.compact
        def __call__(self, x, train: bool = False):
            x = lnn.Conv(16, (3, 3), strides=2, use_bias=False)(x)
            x = lnn.BatchNorm(use_running_average=not train)(x)
            x = lnn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            x = lnn.relu(lnn.Dense(256)(x))
            return lnn.Dense(self.num_classes)(x)

    model = (
        ViT(depth=8, dim=32, heads=2, patch=8)
        if model_kind == "vit"
        else ParityNet()
    )
    hp = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "1",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--parity-check", "3",
            "--ckpt-path", ckpt_dir,
            *extra,
        ],
    )
    trainer = Trainer(hp, model=model)
    try:
        trainer.fit()
    finally:
        trainer.close()


def bench_parity(out_path: str = "BENCH_PARITY.json") -> dict:
    """The parity leg (ISSUE 16): run the eager-parity rail across every
    layout class the planner can emit and commit the verdicts.

    Eight child runs on a forced 4-device axis, each a real Trainer run
    with ``--parity-check 3``: the rail records the first 3 live steps,
    replays them through a fresh instance of the same scanned executable
    family (bitwise replay gate), and diffs them against the no-jit eager
    reference under the leg's calibrated scale-aware ulp tolerance.  Legs:

    - ``dp4`` / ``zero`` — plain data parallel and ``--shard-optim``:
      fp32 reassociation only, tight ``ulp=1024`` tolerance;
    - ``fp16`` / ``int8`` — compressed wire: the quantize boundary's
      scale reduction reorders under XLA fusion, so whole quantization
      buckets flip — calibrated tolerances are measured, not guessed;
    - ``tp2`` / ``pp2_interleaved`` — GSPMD matmul contraction splits and
      microbatch grad averaging reassociate the most (the repo's own
      pipeline pins accept atol 5e-4 on the loss — same physics);
    - ``pp2_wire_fp16`` — the wire-true compressed pipeline: the eager
      rail doesn't model the in-schedule residual, so the reference gate
      must report ``unsupported`` while the bitwise replay gate stays
      green;
    - ``corrupt`` — ``--parity-corrupt 1:7:Dense``: a single injected
      bit-flip that the replay gate must localize to exactly (step 1,
      relayout stage, the Dense leaf), proving the bisection finds real
      silicon faults and not just synthetic ones.

    Every leg self-validates (``run_report --check`` + a required
    ``parity`` kind) and is re-gated through the user-facing
    ``run_report.py --parity`` view, so the committed JSON proves the
    whole rail — capture, replay, bisect, render — not just the engine.
    """
    import io
    import json
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    # (model, trainer flags, expectation) per leg.  Tolerances are
    # calibrated: run once with a loose tol, read max_ulp off the event,
    # pick the next power of two with >=4x headroom (see README).
    legs = {
        "dp4": ("conv", ["--parity-tol", "ulp=1024"], "ok"),
        "zero": (
            "conv",
            ["--shard-optim", "--parity-tol", "ulp=1024"],
            "ok",
        ),
        "fp16": (
            "conv",
            ["--grad-comms", "fp16", "--parity-tol", f"ulp={1 << 27}"],
            "ok",
        ),
        "int8": (
            "conv",
            ["--grad-comms", "int8", "--parity-tol", f"ulp={1 << 27}"],
            "ok",
        ),
        "tp2": (
            "vit",
            ["--model-parallel", "2", "--parallel-style", "tensor",
             "--parity-tol", f"ulp={1 << 27}"],
            "ok",
        ),
        "pp2_interleaved": (
            "vit",
            ["--model-parallel", "2", "--parallel-style", "pipeline",
             "--pipeline-schedule", "interleaved",
             "--pipeline-virtual-stages", "2",
             "--pipeline-microbatches", "2",
             "--parity-tol", f"ulp={1 << 27}"],
            "ok",
        ),
        "pp2_wire_fp16": (
            # wire-true needs the 1f1b family: only a schedule that owns
            # its backward carries the in-schedule EF residual the eager
            # rail can't model (plain GPipe-style pipeline + --grad-comms
            # routes the wire through the ordinary comms plan, which the
            # rail DOES cover — that combination is just another ok leg)
            "vit",
            ["--model-parallel", "2", "--parallel-style", "pipeline",
             "--pipeline-schedule", "1f1b",
             "--pipeline-microbatches", "2",
             "--grad-comms", "fp16",
             "--parity-tol", f"ulp={1 << 27}"],
            "unsupported_reference",
        ),
        "corrupt": (
            "conv",
            ["--parity-corrupt", "1:7:Dense", "--parity-tol", "ulp=1024"],
            "localized",
        ),
    }
    _require_explicit_cpu("--parity")
    env = forced_host_device_env(4)
    results: dict = {}
    worst_rc = 0
    sweep_ok = True
    for leg, (model_kind, flags, expect) in legs.items():
        ckpt = tempfile.mkdtemp(prefix=f"parity-bench-{leg}-")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--parity-child", model_kind, ckpt, *flags],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"parity bench leg {leg} failed ({proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        rc = events_check_rc(ckpt, require_kinds=("parity",))
        worst_rc = max(worst_rc, rc)
        sink = io.StringIO()
        parity_rc = run_report.parity_report(
            ckpt, out=lambda s: sink.write(str(s) + "\n")
        )
        events, _files = run_report.load_run(ckpt)
        payload = next(
            run_report._payload(ev)
            for ev in events
            if ev.get("kind") == "parity"
        )
        rdiv = payload.get("replay_divergence") or {}
        if expect == "ok":
            leg_ok = payload.get("verdict") == "ok" and parity_rc == 0
        elif expect == "unsupported_reference":
            leg_ok = (
                payload.get("replay") == "ok"
                and payload.get("eager_reference") == "unsupported"
                and parity_rc == 0
            )
        else:  # localized: the injected flip named exactly
            leg_ok = (
                parity_rc == 1
                and rdiv.get("step") == 1
                and rdiv.get("stage") == "relayout"
                and "Dense" in str(rdiv.get("leaf", ""))
            )
        sweep_ok = sweep_ok and leg_ok
        results[leg] = {
            "flags": flags,
            "expect": expect,
            "leg_ok": leg_ok,
            "mode": payload.get("mode"),
            "steps": payload.get("steps"),
            "tol": payload.get("tol"),
            "layout": payload.get("layout"),
            "replay": payload.get("replay"),
            "eager_reference": payload.get("eager_reference"),
            "max_ulp": payload.get("max_ulp"),
            "verdict": payload.get("verdict"),
            "replay_divergence": payload.get("replay_divergence"),
            "run_report_parity_rc": parity_rc,
            "events_check_rc": rc,
        }

    record = {
        "world": {"devices": 4, "data_axis": "layout-dependent",
                  "platform": "cpu"},
        "legs": results,
        "sweep_ok": sweep_ok,
        "events_check_rc": worst_rc,
        "note": (
            "CPU capture: the replay gate's bitwise verdicts and the "
            "corruption localization are silicon-independent claims; the "
            "reference-gate max_ulp columns are CPU-fusion figures — "
            "recalibrate tolerances once on a TPU pod (same loose-tol "
            "procedure) before gating there."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(
        {
            "key": "parity",
            "sweep_ok": sweep_ok,
            "verdicts": {
                leg: r["verdict"] for leg, r in results.items()
            },
            "max_ulp": {leg: r["max_ulp"] for leg, r in results.items()},
            "events_check_rc": worst_rc,
        },
        sort_keys=True,
    ))
    return record


def _bench_relayout_child(argv) -> None:
    """One relayout-bench leg in a FRESH process (the parent forces the
    virtual device count before jax initializes here): a real interleaved
    Trainer run — resident chunk view by default, the legacy per-step
    relayout under ``--no-pipeline-resident-layout`` — that writes the
    CANONICAL final-params fingerprint to ``CKPT_DIR/relayout_fp.json``
    so the parent can compare trajectories across legs bitwise.  argv:
    ``CKPT_DIR [trainer flags...]``."""
    import os

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.health.desync import (
        fingerprint_leaves,
        fold_fingerprint,
    )
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.parallel import layouts
    from distributed_training_comparison_tpu.train import Trainer

    ckpt_dir, extra = argv[0], list(argv[1:])
    hp = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "1",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--pipeline-parallel", "4",
            "--pipeline-schedule", "interleaved",
            "--pipeline-virtual-stages", "2",
            "--pipeline-microbatches", "4",
            "--ckpt-path", ckpt_dir,
            *extra,
        ],
    )
    trainer = Trainer(hp, model=ViT(depth=8, dim=32, heads=2, patch=8))
    try:
        trainer.fit()
        # the cross-leg comparison frame: whatever layout this leg
        # carried resident, read the trunk through the canonical view
        canonical = layouts.state_to_canonical(
            trainer.state, trainer._state_layout
        )
        paths, sums = fingerprint_leaves(jax.device_get(canonical.params))
        record = {
            "state_layout": trainer._state_layout.tag,
            "fingerprint": int(fold_fingerprint(sums)),
            "n_leaves": len(paths),
        }
    finally:
        trainer.close()
    with open(os.path.join(ckpt_dir, "relayout_fp.json"), "w") as f:
        json.dump(record, f)


def bench_relayout(out_path: str = "BENCH_RELAYOUT.json") -> dict:
    """The schedule-native state-layout leg (ISSUE 19): prove the
    interleaved hot path carries the chunk view resident — no per-step
    relayout — and that deleting the relayout changed no values.

    Three child runs of the same interleaved v=2 x pipe=4 training job on
    a forced 4-device axis:

    - ``resident`` — the default: ``TrainState.params['blocks']`` lives in
      the schedule's ``(v, P, K, ...)`` chunk view; the step executable
      indexes chunks directly.
    - ``legacy`` — ``--no-pipeline-resident-layout``: the pre-ISSUE-19
      path, the contiguous stack re-laid (reshape + sharding constraint)
      inside EVERY step.
    - ``parity`` — the resident leg re-run under ``--parity-check 3``: the
      capture -> replay rail's bitwise gate over the live resident
      trajectory, re-gated through ``run_report --parity``.

    Committed evidence, all from the event stream (the same ledger
    ``run_report --compute`` renders):

    - the chunk-runner executables' compile-ledger ``temp_bytes`` /
      ``argument_bytes`` per leg — the legacy leg's per-step relayout
      shows up as temp-buffer traffic the resident leg simply does not
      have;
    - per-dispatch step seconds per leg (CPU wall numbers — directional
      on this backend, the ledger bytes are the load-bearing claim);
    - the CANONICAL final-params fingerprint of each leg: resident ==
      legacy bitwise, so the relayout was deleted, not approximated.
    """
    import io
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    legs = {
        "resident": [],
        "legacy": ["--no-pipeline-resident-layout"],
        "parity": ["--parity-check", "3"],
    }
    _require_explicit_cpu("--relayout")
    env = forced_host_device_env(4)
    results: dict = {}
    worst_rc = 0
    for leg, flags in legs.items():
        ckpt = tempfile.mkdtemp(prefix=f"relayout-bench-{leg}-")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--relayout-child", ckpt, *flags],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"relayout bench leg {leg} failed ({proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        rc = events_check_rc(ckpt, require_kinds=("compile",))
        worst_rc = max(worst_rc, rc)
        events, _files = run_report.load_run(ckpt)
        comp = run_report.compute_summary(events)
        # the step family: every chunk-runner executable (full chunk +
        # remainder lengths compile separately)
        step_rows = [
            r for r in comp["rows"] if "chunk_runner" in r["name"]
        ]
        dispatch_s = sum(r["dispatch_s"] for r in step_rows)
        dispatches = sum(r["dispatches"] for r in step_rows)
        # the memory side of the ledger straight off the compile events
        # (compute_summary keeps only the peak fold)
        ledger = {"temp_bytes": 0, "argument_bytes": 0, "output_bytes": 0}
        seen: set = set()
        for ev in events:
            if ev.get("kind") != "compile":
                continue
            p = run_report._payload(ev)
            if "chunk_runner" not in str(p.get("name", "")):
                continue
            fp = p.get("fingerprint")
            if fp in seen:
                continue
            seen.add(fp)
            for k in ledger:
                ledger[k] += int(p.get(k, 0) or 0)
        with open(os.path.join(ckpt, "relayout_fp.json")) as f:
            fp_record = json.load(f)
        row = {
            "flags": flags,
            "state_layout": fp_record["state_layout"],
            "final_params_fingerprint": fp_record["fingerprint"],
            "step_executables": len(step_rows),
            "dispatches": dispatches,
            "dispatch_s": round(dispatch_s, 6),
            "per_dispatch_s": (
                round(dispatch_s / dispatches, 6) if dispatches else None
            ),
            "ledger": ledger,
            "events_check_rc": rc,
        }
        if leg == "parity":
            sink = io.StringIO()
            row["run_report_parity_rc"] = run_report.parity_report(
                ckpt, out=lambda s: sink.write(str(s) + "\n")
            )
            payload = next(
                (run_report._payload(ev) for ev in events
                 if ev.get("kind") == "parity"),
                {},
            )
            row["parity_verdict"] = payload.get("verdict")
            row["parity_replay"] = payload.get("replay")
        results[leg] = row

    resident, legacy = results["resident"], results["legacy"]
    fingerprint_match = (
        resident["final_params_fingerprint"]
        == legacy["final_params_fingerprint"]
    )
    temp_delta = (
        legacy["ledger"]["temp_bytes"] - resident["ledger"]["temp_bytes"]
    )
    parity_ok = (
        results["parity"].get("parity_verdict") == "ok"
        and results["parity"].get("run_report_parity_rc") == 0
    )
    ok = (
        fingerprint_match
        and parity_ok
        and resident["state_layout"].startswith("chunked:")
        and legacy["state_layout"] == "contiguous"
        and worst_rc == 0
    )
    record = {
        "world": {"devices": 4, "layout": "pipe=4 x virtual=2",
                  "platform": "cpu"},
        "legs": results,
        "comparison": {
            "fingerprint_match": fingerprint_match,
            "temp_bytes_delta_legacy_minus_resident": temp_delta,
            "dispatch_s_ratio_legacy_over_resident": (
                round(legacy["dispatch_s"] / resident["dispatch_s"], 3)
                if resident["dispatch_s"] > 0
                else None
            ),
            "parity_ok": parity_ok,
        },
        "ok": ok,
        "events_check_rc": worst_rc,
        "note": (
            "CPU capture: the fingerprint/parity bitwise claims and the "
            "compile-ledger byte deltas are silicon-independent; the "
            "dispatch-seconds columns are CPU wall figures — re-run on a "
            "TPU pod for the headline step-time delta."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(
        {
            "key": "relayout",
            "ok": ok,
            "fingerprint_match": fingerprint_match,
            "temp_bytes": {
                leg: results[leg]["ledger"]["temp_bytes"]
                for leg in ("resident", "legacy")
            },
            "per_dispatch_s": {
                leg: results[leg]["per_dispatch_s"]
                for leg in ("resident", "legacy")
            },
            "parity_verdict": results["parity"].get("parity_verdict"),
            "events_check_rc": worst_rc,
        },
        sort_keys=True,
    ))
    return record


def _bench_plan_child(argv) -> None:
    """One plan-bench leg in a FRESH process (the parent forces the
    virtual device count before jax initializes here): a real Trainer run
    of a small dense ViT whose head/depth arithmetic leaves the planner a
    REAL layout space on 4 devices (dp4 / dp2×tp2 / dp2×pp2 / dp1×pp4 ×
    ZeRO × wire tiers) — argv: ``CKPT_DIR [trainer flags...]``."""
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    ckpt_dir, extra = argv[0], list(argv[1:])
    hp = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "2",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "4", "--metrics-flush-steps", "4",
            "--ckpt-path", ckpt_dir,
            *extra,
        ],
    )
    trainer = Trainer(hp, model=ViT(depth=4, dim=64, heads=2))
    try:
        trainer.fit()
    finally:
        trainer.close()


def bench_plan(out_path: str = "BENCH_PLAN.json") -> dict:
    """The planner leg (ISSUE 14): race the auto-parallel planner's pick
    against hand-tuned layouts through the real Trainer, on the SAME
    ledger capture, and prove the elastic replan loop.

    Phases (each child a fresh process on a forced 4-device CPU world):

    1. **capture** — a hand-default (pure DP, the committed BENCH_r0x
       shape) run whose compile events + dispatch sketches become the
       ledger the planner fits;
    2. **hand legs** — the layout flag sets an operator would hand-tune
       (dp4, dp2×tp2, dp2×pp2), each measured with the same instrument
       (``planner.fit_ledger``'s seconds-per-step off the committed
       stream — never a stopwatch the events can't reproduce);
    3. **plan leg** — ``--parallel-plan auto`` pointed at the capture
       root: the planner fits the ledger, installs its pick, and the
       measured step seconds race the best hand leg
       (``plan_vs_best_hand`` ≤ parity);
    4. **fleet resize leg** — ``--supervise --fleet-hosts 2
       --parallel-plan auto`` loses host 1 to a SIGKILL: the stream must
       show ``resize`` → ``plan`` with a CHOSEN LAYOUT THAT DIFFERS from
       the pre-shrink one (the shrunk fleet lands on the best legal
       layout, not the widest), ``run_report --plan`` green.

    Every leg self-validates (``--check``); the plan-bearing legs require
    the ``plan`` kind so a silently-skipped planner can't commit a
    capture.  CPU caveat: host==device silicon means measured parity, not
    speedups, is what binds here — the committed claim is that the
    planner's pick is never slower than hand-tuning at parity tolerance,
    and that the decision chain (ledger → fit → plan → install →
    run_start) is intact end-to-end.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile
    import threading

    from distributed_training_comparison_tpu.parallel import planner
    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    _require_explicit_cpu("--plan")
    env = forced_host_device_env(4)
    worst_rc = 0

    def run_leg(name: str, ckpt: str, flags: list, require=("compile",)):
        nonlocal worst_rc
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--plan-child", ckpt, *flags],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"plan bench leg {name} failed ({proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        rc = events_check_rc(ckpt, require_kinds=require)
        worst_rc = max(worst_rc, rc)
        # measure THIS leg only: its own (newest) version dir's stream —
        # the plan leg shares its root with the capture, and a root-wide
        # sketch merge would blend the two legs' dispatch seconds
        import pathlib

        vdirs = sorted(pathlib.Path(ckpt).glob("version-*"))
        events = planner.load_ledger_events(vdirs[-1] if vdirs else ckpt)
        fit = planner.fit_ledger(events)
        losses = [
            run_report._payload(e)["train_loss"]
            for e in events
            if e.get("kind") == "epoch_end"
        ]
        return {
            "flags": flags,
            "measured_step_s": (
                round(fit.measured_step_s, 6) if fit.measured_step_s else None
            ),
            "epoch_train_loss": [round(float(l), 6) for l in losses],
            "events_check_rc": rc,
        }, events

    # 1. the ledger capture: hand-default pure DP (the BENCH_r0x shape)
    capture_root = tempfile.mkdtemp(prefix="plan-bench-capture-")
    capture, _ = run_leg("capture", capture_root, [])

    # 2. hand-tuned layouts an operator would race by hand
    hand_flags = {
        "r0x_dp4": [],
        "r0x_dp2_tp2": ["--model-parallel", "2"],
        "r0x_dp2_pp2": ["--pipeline-parallel", "2"],
    }
    hand: dict = {"r0x_dp4": capture}
    for name, flags in hand_flags.items():
        if name in hand:
            continue
        hand[name], _ = run_leg(
            name, tempfile.mkdtemp(prefix=f"plan-bench-{name}-"), flags
        )

    # 3. the plan leg, fit against the capture's ledger (same root: the
    # planner reads every events*.jsonl under --ckpt-path)
    plan_leg, plan_events = run_leg(
        "plan", capture_root, ["--parallel-plan", "auto"],
        require=("compile", "plan"),
    )
    plan_evs = [e for e in plan_events if e.get("kind") == "plan"]
    plan_payload = run_report._payload(plan_evs[-1]) if plan_evs else {}
    plan_gate_rc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "run_report.py"),
         capture_root, "--plan"],
    ).returncode
    worst_rc = max(worst_rc, plan_gate_rc)

    best_hand = min(
        (leg for leg in hand.items() if leg[1]["measured_step_s"]),
        key=lambda kv: kv[1]["measured_step_s"],
    )
    ratio = (
        plan_leg["measured_step_s"] / best_hand[1]["measured_step_s"]
        if plan_leg["measured_step_s"] and best_hand[1]["measured_step_s"]
        else None
    )

    # 4. the fleet resize leg: SIGKILL host 1 after the first verified
    # checkpoint; the shrunk attempt must re-plan onto a DIFFERENT layout
    fleet_root = tempfile.mkdtemp(prefix="plan-bench-fleet-")
    child = os.path.join(repo, "tests", "fleet_pool_worker.py")
    cmd = [
        sys.executable, child, "--supervise",
        "--fleet-hosts", "2", "--fleet-local-devices", "2",
        "--fleet-grace-secs", "3", "--fleet-poll-secs", "0.2",
        "--parallel-plan", "auto",
        "--synthetic-data", "--limit-examples", "1024",
        "--batch-size", "32", "--epoch", "40",
        "--ckpt-path", fleet_root,
        "--save-last-min-secs", "0", "--no-progress",
        "--seed", "7", "--eval-step", "1000",
        "--device-chunk-steps", "8",
        "--heartbeat-secs", "0.5",
        "--goodput-json", os.path.join(fleet_root, "goodput.json"),
    ]
    driver_log: list = []
    proc = subprocess.Popen(
        cmd, cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
        # every child inherits 2 forced CPU devices — the count
        # --fleet-local-devices promises the supervisor, so rank 0's mesh
        # matches the plan's per-host slice (run_report --plan scales the
        # data axis by the world share the emulation's rank 0 joined)
        env=forced_host_device_env(2),
    )
    driver = threading.Thread(
        target=_drive_fleet_gauntlet,
        args=(fleet_root, proc, driver_log, False), daemon=True,
    )
    driver.start()
    out, err = proc.communicate()
    driver.join(timeout=10.0)
    emit_progress(
        "plan_fleet",
        {"rc": proc.returncode, "driver": driver_log,
         "tail": (out or "")[-300:]},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"plan fleet leg failed (rc={proc.returncode}; driver: "
            f"{driver_log}): {(err or '')[-2000:]}"
        )
    fleet_rc = events_check_rc(
        fleet_root, require_kinds=("compile", "resize", "plan")
    )
    worst_rc = max(worst_rc, fleet_rc)
    fleet_plan_gate = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "run_report.py"),
         fleet_root, "--plan"],
    ).returncode
    worst_rc = max(worst_rc, fleet_plan_gate)
    fleet_events = planner.load_ledger_events(fleet_root)
    fleet_plans = [
        run_report._payload(e) for e in fleet_events if e.get("kind") == "plan"
    ]
    fleet_resizes = [
        run_report._payload(e) for e in fleet_events
        if e.get("kind") == "resize"
    ]
    layouts = [p.get("layout") for p in fleet_plans]
    layout_changed = len({json.dumps(l, sort_keys=True) for l in layouts}) > 1
    # the acceptance ordering: a resize event, then a plan whose layout
    # differs from the pre-shrink plan's
    resize_then_replan = bool(
        fleet_resizes and len(fleet_plans) >= 2 and layout_changed
    )

    record = {
        "metric": "auto_parallel_plan_race",
        "world": {"devices": 4, "platform": "cpu",
                  "model": "ViT(depth=4, dim=64, heads=2)"},
        "capture_root_note": (
            "hand r0x_dp4 leg doubles as the ledger capture the plan leg "
            "fits against (same events root)"
        ),
        "legs": {**hand, "plan": plan_leg},
        "plan": {
            "chosen": plan_payload.get("chosen"),
            "layout": plan_payload.get("layout"),
            "predicted_step_s": plan_payload.get("predicted_step_s"),
            "fit": plan_payload.get("fit"),
            "candidates_considered": plan_payload.get("candidates_considered"),
            "candidates": plan_payload.get("candidates"),
            "measured_step_s": plan_leg["measured_step_s"],
            "plan_gate_rc": plan_gate_rc,
        },
        "race": {
            "best_hand": best_hand[0],
            "best_hand_step_s": best_hand[1]["measured_step_s"],
            "plan_step_s": plan_leg["measured_step_s"],
            "plan_vs_best_hand": round(ratio, 4) if ratio else None,
            # CPU parity tolerance: single shared core, ~25% jitter
            "parity_ok": bool(ratio is not None and ratio <= 1.25),
        },
        "fleet": {
            "script": "SIGKILL host 1 after the first verified ckpt -> "
                      "shrink -> re-plan",
            "driver": driver_log,
            "resizes": [
                (r.get("from_world"), r.get("to_world"), r.get("reason"))
                for r in fleet_resizes
            ],
            "plans": [
                {
                    "attempt": p.get("attempt"),
                    "reason": p.get("reason"),
                    "chosen": (p.get("chosen") or {}).get("key"),
                    "layout": p.get("layout"),
                    "predicted_step_s": p.get("predicted_step_s"),
                }
                for p in fleet_plans
            ],
            "layout_changed_on_resize": resize_then_replan,
            "events_check_rc": fleet_rc,
            "plan_gate_rc": fleet_plan_gate,
        },
        "events_check_rc": worst_rc,
        "note": (
            "CPU capture: host==device silicon, so measured PARITY (not "
            "speedup) is what binds — the committed claims are (a) the "
            "planner's ledger-fit pick races the best hand-tuned layout "
            "at parity tolerance, and (b) the elastic loop re-plans on "
            "resize onto a different legal layout, with the whole "
            "decision chain (ledger -> fit -> plan event -> installed "
            "flags -> run_start) validated by run_report --plan. "
            "Recapture on a TPU pod for binding speedups."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(
        {
            "key": "plan",
            "chosen": (plan_payload.get("chosen") or {}).get("key"),
            "race": record["race"],
            "fleet_resizes": record["fleet"]["resizes"],
            "fleet_layout_changed": resize_then_replan,
            "events_check_rc": worst_rc,
            "full_record": out_path,
        },
        sort_keys=True,
    ))
    return record


def _bench_pipeline_child(argv) -> None:
    """The pipeline timing leg, run in a FRESH process under a forced
    8-device CPU topology (2 data × 4 pipe): for each schedule, measure
    the fwd+bwd step at M and 2M microbatches and fit the measured bubble
    fraction from the two points — ``slope = (t(2M) - t(M)) / M`` is the
    marginal per-microbatch cost, so ``bubble = (t(M) - M·slope) / t(M)``
    is the fraction of the step that is warmup/cooldown, MEASURED rather
    than derived.  Also: one SGD step per schedule from the same init
    (final-params parity vs the unpipelined baseline) and the compiled
    flops of the 1F1B executable with and without the head-on-every-stage
    formulation (the ISSUE-12 satellite fix priced in the same ledger
    units the compile events use).  argv: ``[OUT_JSON]``."""
    import json as _json

    import optax

    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.parallel import (
        make_interleaved_fwd_bwd,
        make_mesh,
        pipelined_vit_apply,
        schedule_meta,
    )
    from distributed_training_comparison_tpu.parallel.mesh import PIPE_AXIS

    out_path = argv[0]
    mesh = make_mesh(8, 1, 4)  # 2 data × 4 pipe
    p_size = 4
    m_base = 8
    model = ViT(depth=8, dim=64, heads=4, patch=8)
    x = jax.random.normal(jax.random.key(1), (64, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x, train=False)
    params = variables["params"]
    labels = jax.random.randint(jax.random.key(3), (64,), 0, 100)
    tx = optax.sgd(0.01)
    opt0 = tx.init(params)

    def direct_loss(p):
        logits = model.apply({"params": p}, x, train=True)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        return ce.mean()

    def one_sgd(g):
        updates, _ = tx.update(g, opt0, params)
        return optax.apply_updates(params, updates)

    def fwd_bwd_for(schedule: str, m: int):
        if schedule == "gpipe":
            def fb(p, xx, ll):
                def loss(pp):
                    logits = pipelined_vit_apply(
                        model, {"params": pp}, xx, mesh,
                        num_microbatches=m, pipe_axis=PIPE_AXIS,
                    )
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        logits, ll
                    )
                    return ce.mean()

                return jax.value_and_grad(loss)(p)

            return jax.jit(fb)
        v = 2 if schedule == "interleaved" else 1
        inner = make_interleaved_fwd_bwd(
            model, mesh, num_microbatches=m, virtual=v, pipe_axis=PIPE_AXIS,
        )
        return jax.jit(lambda p, xx, ll: inner(p, xx, ll)[::2])  # (loss, grads)

    def timed(fn, reps: int = 5) -> float:
        # best-of-N: the two-point bubble fit divides small differences,
        # so a background-load outlier in EITHER measurement would swamp
        # the slope — minimum wall time is the noise-robust estimator
        fn(params, x, labels)[0].block_until_ready()  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            loss, _ = fn(params, x, labels)
            loss.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    g_base = jax.jit(jax.value_and_grad(direct_loss))(params)[1]
    p_base = jax.device_get(one_sgd(g_base))
    schedules: dict = {}
    for schedule in ("gpipe", "1f1b", "interleaved"):
        fb_m = fwd_bwd_for(schedule, m_base)  # one compile, timed + parity
        t_m = timed(fb_m)
        t_2m = timed(fwd_bwd_for(schedule, 2 * m_base))
        slope = max(1e-9, (t_2m - t_m) / m_base)
        bubble_meas = max(0.0, (t_m - m_base * slope) / t_m)
        meta = schedule_meta(
            schedule, p_size, m_base, 2 if schedule == "interleaved" else 1
        )
        _, g = fb_m(params, x, labels)
        p_new = jax.device_get(one_sgd(g))
        parity = max(
            jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(
                    lambda a, b: float(jnp.max(jnp.abs(a - b))), p_base, p_new
                )
            )
        )
        schedules[schedule] = {
            "step_s_at_m": round(t_m, 4),
            "step_s_at_2m": round(t_2m, 4),
            "per_microbatch_s": round(slope, 6),
            "bubble_frac_measured": round(bubble_meas, 4),
            "bubble_frac_schedule": meta["bubble_frac"],
            "ticks": meta["ticks"],
            "useful_ticks": meta["useful_ticks"],
            "virtual": meta["virtual"],
            "final_params_max_abs_vs_unpipelined": parity,
        }

    # the head-cond satellite, priced in ledger units: compiled flops of
    # the 1F1B step with the fixed last-stage-only head vs the pre-fix
    # head-on-every-stage formulation
    def flops_of(head_all):
        inner = make_interleaved_fwd_bwd(
            model, mesh, num_microbatches=m_base, virtual=1,
            pipe_axis=PIPE_AXIS, head_all_stages=head_all,
        )
        compiled = (
            jax.jit(lambda p, xx, ll: inner(p, xx, ll)[::2])
            .lower(params, x, labels)
            .compile()
        )
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float((cost or {}).get("flops", 0.0))

    fixed, pre_fix = flops_of(False), flops_of(True)
    record = {
        "world": {"devices": 8, "data": 2, "pipe": 4, "microbatches": m_base},
        "model": {"depth": 8, "dim": 64, "heads": 4},
        "schedules": schedules,
        "head_fix_flops": {
            "head_last_stage_only": fixed,
            "head_every_stage": pre_fix,
            "saved_flops": pre_fix - fixed,
            "saved_frac": round((pre_fix - fixed) / pre_fix, 4)
            if pre_fix
            else None,
        },
    }
    with open(out_path, "w") as f:
        _json.dump(record, f)
    print("PIPELINE_CHILD_OK", flush=True)


def _bench_pipeline_e2e_child(argv) -> None:
    """The pipeline e2e leg: a real DP×TP×PP (2×2×2) Trainer run through
    the full stack — obs on, interleaved schedule, per-stage span lanes,
    per-stage desync fingerprints, per-stage straggler sketches — whose
    event stream the parent self-validates.  argv: ``CKPT_DIR``."""
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    ckpt_dir = argv[0]
    hp = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "320",
            "--batch-size", "64", "--epoch", "2",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "2", "--metrics-flush-steps", "2",
            "--model-parallel", "2", "--pipeline-parallel", "2",
            "--pipeline-schedule", "interleaved",
            "--pipeline-virtual-stages", "2",
            "--pipeline-microbatches", "2",
            "--health-desync-every", "1",
            "--ckpt-path", ckpt_dir,
        ],
    )
    trainer = Trainer(hp, model=ViT(depth=8, dim=32, heads=2, patch=8))
    try:
        trainer.fit()
    finally:
        trainer.close()
    print("PIPELINE_E2E_OK", flush=True)


def bench_pipeline(out_path: str = "BENCH_PIPELINE.json") -> dict:
    """The pipeline leg (ISSUE 12): gpipe vs 1F1B vs interleaved-1F1B at
    fixed (P=4, M=8) — step time, MEASURED bubble fraction (two-point
    microbatch fit), schedule-arithmetic bubble, final-params parity vs
    the unpipelined baseline, and the head-fix flops delta — plus one real
    DP×TP×PP (2×2×2) interleaved Trainer run whose event stream
    self-validates (``--check --require-kind compile --require-kind
    pipeline``) and must carry the per-stage planes: the run_report bubble
    table, per-stage straggler sketches, and the (host, stage) span lanes
    in trace.json."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import run_report

    _require_explicit_cpu("--pipeline")
    env = forced_host_device_env(8)
    timing_json = os.path.join(
        tempfile.mkdtemp(prefix="pipe-bench-"), "timing.json"
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--pipeline-child", timing_json],
        env=env, capture_output=True, text=True, timeout=3000,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"pipeline timing leg failed ({proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    with open(timing_json) as f:
        record = json.load(f)

    ckpt = tempfile.mkdtemp(prefix="pipe-bench-e2e-")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--pipeline-e2e-child", ckpt],
        env=env, capture_output=True, text=True, timeout=3000,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"pipeline e2e leg failed ({proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    rc = events_check_rc(ckpt, require_kinds=("compile", "pipeline"))
    events, _files = run_report.load_run(ckpt)
    comp = run_report.compute_summary(events)
    pipe = comp.get("pipeline") or {}
    merged = run_report.merge_metric_events(
        [e for e in events if e.get("kind") == "metrics"]
    )
    stage_sketches = sorted(
        k for k in merged if k.startswith("step/stage")
    )
    # per-(host, stage) span lanes in the exported trace
    lanes = set()
    import glob as _glob

    for tr in _glob.glob(os.path.join(ckpt, "**", "trace*.json"),
                         recursive=True):
        with open(tr) as f:
            for ev in json.load(f).get("traceEvents", []):
                if ev.get("ph") == "M" and ev.get("name") == "thread_name":
                    name = (ev.get("args") or {}).get("name", "")
                    if name.startswith("stage"):
                        lanes.add(name)
    losses = [
        run_report._payload(e)["train_loss"]
        for e in events
        if e.get("kind") == "epoch_end"
    ]
    record["e2e"] = {
        "flags": "DP2×TP2×PP2 interleaved v=2 M=2",
        "events_check_rc": rc,
        "pipeline_meta": pipe.get("meta"),
        "bubble_table": pipe.get("rows"),
        "stage_sketches": stage_sketches,
        "stage_span_lanes": sorted(lanes),
        "epoch_train_loss": [round(float(l), 6) for l in losses],
    }
    record["events_check_rc"] = rc
    record["note"] = (
        "CPU capture: all 8 'devices' share host cores, so tick wall time "
        "≈ sum of per-stage work rather than max — the measured bubble "
        "fractions bind as RELATIVE ordering (interleaved < 1f1b at fixed "
        "P, M), the schedule-arithmetic fractions as the silicon "
        "prediction; recapture on a TPU pod for binding absolute times. "
        "Parity and the head-fix flops delta are silicon-independent."
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(
        {
            "key": "pipeline",
            "bubble_measured": {
                s: record["schedules"][s]["bubble_frac_measured"]
                for s in record["schedules"]
            },
            "parity_max_abs": {
                s: record["schedules"][s][
                    "final_params_max_abs_vs_unpipelined"
                ]
                for s in record["schedules"]
            },
            "head_fix_saved_frac": record["head_fix_flops"]["saved_frac"],
            "events_check_rc": rc,
        },
        sort_keys=True,
    ))
    return record


def bench_overlap(out_path: str = "BENCH_OVERLAP.json") -> dict:
    """The overlapped-execution leg: how much throughput the streaming path
    gains from double-buffered device prefetch + donated runners, and what
    chunking the device mode costs — committed as ``BENCH_OVERLAP.json``
    (pretty-print / diff two captures with ``tools/overlap_report.py``).

    Host-streaming legs (same loader sequence, same trajectory):

    - ``host_blocking``    — the fully serialized pipeline: synchronous
      batch assembly on the main thread, H2D, dispatch, then BLOCK on the
      chunk's result before assembling the next (what a per-chunk metrics
      read — or any framework without async dispatch — produces: the chip
      idles during every host-side phase);
    - ``host_async``       — the pre-overlap default: assembly on the main
      thread between async dispatches, no per-chunk sync, no donation (the
      chip idles only while the host stacks + transfers);
    - ``host_overlapped``  — ``DevicePrefetcher`` staging (depth 2) +
      donated chunk runner: assembly AND transfer ride a background thread
      while the current chunk computes; the main thread's step-time
      breakdown (h2d-wait / dispatch / compute) is recorded.

    Device-mode legs (same trajectory by the chunk runner's key-fold
    contract): ``device_monolithic`` (one whole-epoch program) vs
    ``device_chunked`` (the chunked path at default chunk = steps/epoch)
    vs ``device_chunked_small`` (chunk-boundary granularity every 8 steps)
    — the acceptance question is that chunking costs ≈ nothing at the
    default and single-digit % at fine granularity.
    """
    from distributed_training_comparison_tpu.data import (
        DeviceDataset,
        DevicePrefetcher,
        HostLoader,
        chunked_batches,
    )
    from distributed_training_comparison_tpu.data.loader import PrefetchLoader
    from distributed_training_comparison_tpu.train import (
        make_chunk_runner,
        make_device_chunk_runner,
    )
    from distributed_training_comparison_tpu.utils import (
        StepTimeMeter,
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    platform = jax.devices()[0].platform
    mesh = parallel.make_mesh(backend="tpu")
    note = None
    if platform == "cpu":
        # CI sizing (2-core container).  The flagship models compile for
        # minutes per executable on this host, and host staging would be
        # an invisible fraction of their compute anyway — so the CPU legs
        # run a purpose-built PROBE model sized so host-side work (gather +
        # stack + device_put of 48 KB/image) is a measurable fraction of
        # device compute.  Caveat recorded in the output: on a CPU-only
        # host, "host" and "device" are the same two cores, so hiding
        # staging behind compute cannot add throughput the way it does on
        # an accelerator (there is no idle chip to recover; the producer
        # thread even steals consumer cores, so some h2d_wait stays
        # exposed) — the mechanism evidence is the perf-marked
        # microbenchmarks, the host-leg ratios here measure scheduling
        # overhead, not the separate-silicon win.  Augmentation is off in
        # every leg: the
        # in-jit crop/flip at 128 px would dwarf both sides of the
        # balance this leg exists to measure.
        model_name, image_size, batch, chunk, n, epochs = (
            "probe_conv", 128, 256, 8, 4_096, 3
        )
        note = (
            "cpu container: host==device silicon, so overlap recovers no "
            "idle chip time; ratios measure pipeline overhead only — see "
            "README 'Overlapped execution'"
        )
    else:
        # steps divisible by chunk: the timed loops must never compile a
        # remainder-shaped executable mid-measurement
        model_name, image_size, batch, chunk, n, epochs = (
            "resnet18", 32, 256, 32, 32_768, 3
        )
    images, labels = synthetic_dataset(
        n, num_classes=100, image_shape=(image_size, image_size, 3), seed=0
    )
    ds = DeviceDataset(images, labels)
    steps = n // batch

    def fresh_state():
        if model_name == "probe_conv":
            import flax.linen as lnn

            class ProbeConv(lnn.Module):
                """Strided conv + head: compute sized to the staging bytes."""

                @lnn.compact
                def __call__(self, x, train: bool = False):
                    x = lnn.Conv(4, (3, 3), strides=8, use_bias=False)(x)
                    x = lnn.relu(x)
                    x = jnp.mean(x, axis=(1, 2))
                    return lnn.Dense(100)(x)

            tx, _ = configure_optimizers(HP, steps_per_epoch=100)
            state = create_train_state(
                ProbeConv(), jax.random.key(0), tx,
                input_shape=(1, image_size, image_size, 3),
            )
            return jax.device_put(state, parallel.replicated_sharding(mesh))
        return _setup(mesh, model_name, "bf16", image_size=image_size)

    precision = "fp32" if platform == "cpu" else "bf16"

    def batches(workers: int):
        loader = HostLoader(ds, batch, shuffle=True, drop_last=True, seed=1)
        loader = PrefetchLoader(loader, depth=workers) if workers else loader
        loader.set_epoch(0)
        return loader

    def place(b):
        return parallel.shard_batch(b, mesh, batch_axis=1)

    def run_host(kind: str) -> dict:
        runner = make_chunk_runner(
            mesh, precision=precision, augment=False,
            donate=(kind == "overlapped"),
        )
        state = fresh_state()
        key = jax.random.key(2)
        meter = StepTimeMeter()
        # warmup: compile the full-chunk (and any remainder-chunk) shape
        warm = 2 * chunk + steps % chunk
        for start, take, b in chunked_batches(iter(batches(0)), warm, chunk):
            pb = place(b)
            state, m = runner(state, pb["x"], pb["y"], key, jnp.asarray(start))
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(epochs):
            loader = batches(0 if kind == "blocking" else 4)
            it = iter(loader)
            if kind == "overlapped":
                chunks = DevicePrefetcher(it, steps, chunk, place, depth=2)
            else:
                chunks = (
                    (s, k, place(b))
                    for s, k, b in chunked_batches(it, steps, chunk)
                )
            try:
                while True:
                    with meter.phase("h2d_wait"):
                        try:
                            start, take, b = next(chunks)
                        except StopIteration:
                            break
                    with meter.phase("dispatch"):
                        state, m = runner(
                            state, b["x"], b["y"], key, jnp.asarray(start)
                        )
                    meter.note_chunk()
                    if kind == "blocking":
                        jax.block_until_ready(m)  # fully serialized pipeline
            finally:
                if kind == "overlapped":
                    chunks.close()
                if hasattr(loader, "close"):
                    loader.close()
        with meter.phase("compute"):
            jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        out = {
            "images_per_sec": round(epochs * steps * batch / dt, 1),
            "wall_s": round(dt, 3),
        }
        if kind == "overlapped":
            out["step_breakdown"] = meter.summary()
        return out

    def run_device(kind: str) -> dict:
        repl = parallel.replicated_sharding(mesh)
        d_images = jax.device_put(images, repl)
        d_labels = jax.device_put(labels, repl)
        key = jax.random.key(2)
        state = fresh_state()
        rem = None
        if kind == "monolithic":
            runner = make_epoch_runner(
                mesh, batch, precision=precision, augment=False
            )
            dispatches = [(steps, 0)]
        else:
            k = chunk if kind == "chunked_small" else steps
            runner = make_device_chunk_runner(
                mesh, batch, k, precision=precision, augment=False
            )
            dispatches = [(k, s) for s in range(0, steps - steps % k, k)]
            if steps % k:
                rem = make_device_chunk_runner(
                    mesh, batch, steps % k, precision=precision, augment=False
                )
                dispatches.append((steps % k, steps - steps % k))

        def one_epoch(state, e):
            for take, start in dispatches:
                r = runner if take == dispatches[0][0] else rem
                if kind == "monolithic":
                    state, m = r(state, d_images, d_labels, key, jnp.asarray(e))
                else:
                    state, m = r(
                        state, d_images, d_labels, key,
                        jnp.asarray(e), jnp.asarray(start),
                    )
            return state, m

        state, m = one_epoch(state, 0)  # warmup: compile + first execution
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for e in range(1, epochs + 1):
            state, m = one_epoch(state, e)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        return {
            "images_per_sec": round(epochs * steps * batch / dt, 1),
            "wall_s": round(dt, 3),
        }

    legs: dict = {}
    for key_, fn in (
        ("host_blocking", lambda: run_host("blocking")),
        ("host_async", lambda: run_host("async")),
        ("host_overlapped", lambda: run_host("overlapped")),
        ("device_monolithic", lambda: run_device("monolithic")),
        ("device_chunked", lambda: run_device("chunked")),
        ("device_chunked_small", lambda: run_device("chunked_small")),
    ):
        try:
            legs[key_] = _attempt(fn)
        except Exception as e:  # evidence over abort, like run_legs
            legs[key_] = {"error": f"{type(e).__name__}: {e}"[:300]}
        emit_progress(key_, legs[key_])

    def ratio(a: str, b: str):
        na = legs.get(a, {}).get("images_per_sec")
        nb = legs.get(b, {}).get("images_per_sec")
        return round(na / nb, 3) if na and nb else None

    record = {
        "metric": "overlapped_execution",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "note": note,
        "model": model_name,
        "batch": batch,
        "image_size": image_size,
        "chunk_steps": chunk,
        "steps_per_epoch": steps,
        "epochs": epochs,
        "legs": legs,
        # the acceptance ratios: prefetch+donation vs the serialized
        # pipeline (and vs the pre-overlap async loop), and what chunking
        # the device mode costs at default / fine granularity
        "overlap_vs_blocking": ratio("host_overlapped", "host_blocking"),
        "overlap_vs_async": ratio("host_overlapped", "host_async"),
        "device_chunked_vs_monolithic": ratio(
            "device_chunked", "device_monolithic"
        ),
        "device_chunked_small_vs_monolithic": ratio(
            "device_chunked_small", "device_monolithic"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "metric": record["metric"],
        "platform": platform,
        "ips": {k: v.get("images_per_sec", "err") for k, v in legs.items()},
        "overlap_vs_blocking": record["overlap_vs_blocking"],
        "overlap_vs_async": record["overlap_vs_async"],
        "device_chunked_vs_monolithic": record["device_chunked_vs_monolithic"],
        "full_record": out_path,
    }))
    return record


if __name__ == "__main__":
    import sys

    if "--serve-cold-child" in sys.argv:
        _bench_serve_cold_child(
            sys.argv[sys.argv.index("--serve-cold-child") + 1:]
        )
    elif "--serve-fleet" in sys.argv:
        bench_serve_fleet()
    elif "--trace" in sys.argv:
        bench_trace()
    elif "--serve" in sys.argv:
        bench_serve()
    elif "--resilience" in sys.argv:
        bench_resilience()
    elif "--chaos" in sys.argv:
        bench_chaos()
    elif "--control" in sys.argv:
        bench_control()
    elif "--health" in sys.argv:
        bench_health()
    elif "--overlap" in sys.argv:
        bench_overlap()
    elif "--obs-overhead" in sys.argv:
        bench_obs_overhead()
    elif "--comms-child" in sys.argv:
        _bench_comms_child(sys.argv[sys.argv.index("--comms-child") + 1:])
    elif "--comms" in sys.argv:
        bench_comms()
    elif "--parity-child" in sys.argv:
        _bench_parity_child(sys.argv[sys.argv.index("--parity-child") + 1:])
    elif "--parity" in sys.argv:
        bench_parity()
    elif "--relayout-child" in sys.argv:
        _bench_relayout_child(
            sys.argv[sys.argv.index("--relayout-child") + 1:]
        )
    elif "--relayout" in sys.argv:
        bench_relayout()
    elif "--plan-child" in sys.argv:
        _bench_plan_child(sys.argv[sys.argv.index("--plan-child") + 1:])
    elif "--plan" in sys.argv:
        bench_plan()
    elif "--pipeline-child" in sys.argv:
        _bench_pipeline_child(sys.argv[sys.argv.index("--pipeline-child") + 1:])
    elif "--pipeline-e2e-child" in sys.argv:
        _bench_pipeline_e2e_child(
            sys.argv[sys.argv.index("--pipeline-e2e-child") + 1:]
        )
    elif "--pipeline" in sys.argv:
        bench_pipeline()
    else:
        main()
