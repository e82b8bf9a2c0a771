"""Bring-up smoke test of the BIP-routed MoE stack on a TPU.

    python chip_smoke.py               # one chip: train, kernels vs reference, serve
    python chip_smoke.py --four-chips  # four chips: minimind-moe-64e expert-parallel training

Every phase runs through the entry points a user calls (`train_loop`,
`ContinuousBatchingEngine`, `repro.kernels.ops`) at the paper's published
widths, with random weights from a fixed seed, and checks its results. Earlier
lines report the device, compile seconds and each phase's results; the last
line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
printed only when every phase passed. Without a TPU, or run away from the
repository's `src/`, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

SEED = 0
# relative step-0 loss gap allowed between expert-parallel training and the
# unsharded forward (see four_chip_phase for how it was set)
FOUR_CHIP_REL_TOL = 1e-4
FOUR_CHIP_MOE_REL_TOL = 5e-2


class SmokeFailure(AssertionError):
    """A phase's output failed its check."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's backend compile time, reset per phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def dual_solver(cfg) -> str:
    """Which BIP dual solver a model built from `cfg` runs."""
    from repro.kernels.platform import interpret_default

    r = cfg.routing
    if r.strategy != "bip":
        return f"{r.strategy} gate (no BIP dual)"
    if r.use_kernel:
        mode = "interpreter" if interpret_default() else "Mosaic"
        return f"Pallas histogram kernel ({mode}), kernels/ops.bip_dual_update"
    if r.sync == "global":
        return "bisection, core/ref_bip.bip_dual_update_global"
    return "sort, core/ref_bip.bip_dual_update"


def _all_finite(tree) -> bool:
    import jax
    import numpy as np

    return all(bool(np.all(np.isfinite(np.asarray(x)))) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------------- train


def train_phase(cfg, *, batch: int, seq_len: int, steps: int, micro: int, mesh=None):
    """`train_loop` for `steps` steps; every loss and router dual q finite."""
    from repro.data.synthetic import SyntheticBatchStream
    from repro.distributed.sharding import make_mesh_ctx
    from repro.models import build_model
    from repro.training import train_loop

    model = build_model(cfg, make_mesh_ctx(mesh))
    stream = SyntheticBatchStream(cfg, batch, seq_len, steps, seed=SEED)
    state, log = train_loop(
        model, stream, lr=1e-3, warmup_steps=2, total_steps=steps,
        microbatches=micro, mesh=mesh, log_every=1,
    )
    losses = [float(v) for v in log.losses]
    max_vio = [float(v.max()) for v in log.max_vio_steps]
    check(len(losses) == steps, f"ran {len(losses)} of {steps} steps")
    check(all(v == v and abs(v) != float("inf") for v in losses), f"losses {losses}")
    check(_all_finite(state.router_states), "router dual q not finite")
    times = list(log.step_times)
    return {
        "arch": cfg.name,
        "dual_solver": dual_solver(cfg),
        "tokens_per_step": batch * seq_len,
        "microbatches": micro,
        "losses": losses,
        "max_vio_per_step": max_vio,
        "step0_seconds_incl_compile": times[0],
        "later_step_seconds": times[1:],
    }


# ----------------------------------------------------------------- kernels


def _softmax_scores(rng, n: int, m: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    logits = rng.standard_normal((n, m)) + 1.5 * np.linspace(2, -2, m)[None, :]
    return jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)


def _mosaic(fn, *args) -> bool:
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def kernel_phase(cfg, *, n_tokens: int, expect_mosaic: bool):
    """The Pallas kernels against their references at `cfg`'s widths.

    Expert FFN (bf16 in, f32 accumulation) vs the model's einsum path on the
    same bf16 operands, forward and the custom_vjp backward: relative
    Frobenius error <= 2e-2. BIP duals vs the exact sort form: |dq| <=
    2/512 + 5e-3 (one coarse histogram bin plus interpolation slack, the
    bound tests/test_kernels.py uses), unmasked and with a serving mask.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import balance_metrics, bip_topk, ref_bip
    from repro.kernels import ops
    from repro.models import moe

    r = cfg.routing
    m, k, d, f = r.n_experts, r.top_k, cfg.d_model, cfg.moe_d_ff
    cap = moe.expert_capacity(n_tokens, cfg)
    rng = np.random.default_rng(SEED)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((m, cap, d)), bf)
    wg = jnp.asarray(rng.standard_normal((m, d, f)) / np.sqrt(d), bf)
    wu = jnp.asarray(rng.standard_normal((m, d, f)) / np.sqrt(d), bf)
    wd = jnp.asarray(rng.standard_normal((m, f, d)) / np.sqrt(f), bf)
    einsum_cfg = dataclasses.replace(
        cfg, compute_dtype=bf, routing=dataclasses.replace(r, use_kernel=False)
    )

    def einsum_ffn(*a):
        return moe._expert_ffn(a[1], a[2], a[3], a[0], einsum_cfg)

    def rel(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    out = {"width": f"m={m} k={k} C={cap} D={d} F={f}"}
    y_k = jax.jit(ops.expert_ffn)(x, wg, wu, wd)
    y_r = jax.jit(einsum_ffn)(x, wg, wu, wd)
    out["ffn_fwd_rel_err"] = rel(y_k, y_r)
    cot = jnp.asarray(rng.standard_normal(y_r.shape), bf)
    grad = lambda fn: jax.jit(jax.grad(
        lambda c, *a: jnp.sum(fn(*a).astype(jnp.float32) * c), argnums=(1, 2, 3, 4)
    ))
    g_k = grad(ops.expert_ffn)(cot, x, wg, wu, wd)
    g_r = grad(einsum_ffn)(cot, x, wg, wu, wd)
    out["ffn_grad_rel_err"] = max(rel(a, b) for a, b in zip(g_k, g_r))
    check(out["ffn_fwd_rel_err"] <= 2e-2, f"expert_ffn forward off: {out}")
    check(out["ffn_grad_rel_err"] <= 2e-2, f"expert_ffn grad off: {out}")

    s = _softmax_scores(rng, n_tokens, m)
    q0 = jnp.zeros((m,), jnp.float32)
    mask = jnp.asarray(rng.random(n_tokens) < 0.5)
    dual_k = jax.jit(lambda s, q, msk: ops.bip_dual_update(
        s, q, top_k=k, n_iters=r.bip_iters, token_mask=msk))
    dual_r = jax.jit(lambda s, q, msk: ref_bip.bip_dual_update(
        s, q, top_k=k, n_iters=r.bip_iters, token_mask=msk)[0])
    tol = 2.0 / 512 + 5e-3
    for name, msk in (("dual", None), ("dual_masked", mask)):
        q_k = dual_k(s, q0, msk)
        q_r = dual_r(s, q0, msk)
        err = float(jnp.max(jnp.abs(q_k - q_r)))
        sel = slice(None) if msk is None else np.asarray(msk)
        vio = [
            float(balance_metrics(bip_topk(s[sel], q, k)[1], m, k)["max_vio"])
            for q in (q_k, q_r)
        ]
        out[f"{name}_max_abs_dq"] = err
        out[f"{name}_max_vio_kernel_vs_ref"] = vio
        check(err <= tol, f"{name}: kernel q off the reference by {err}")
        check(vio[0] <= 1.3 * vio[1] + 0.3, f"{name}: kernel routing unbalanced {vio}")
    if expect_mosaic:
        check(_mosaic(ops.expert_ffn, x, wg, wu, wd), "expert_ffn: no Mosaic kernel")
        check(_mosaic(lambda s, q: ops.bip_dual_update(
            s, q, top_k=k, n_iters=r.bip_iters), s, q0), "bip_dual_update: no Mosaic kernel")
    return out


# ------------------------------------------------------------------- serve


def _topk_twin(cfg):
    """The same model with a per-token top-k gate and dropless capacity.

    A BIP gate prices experts over each step's whole token set, so a served
    stream has no single-sequence reference; this twin routes every token on
    its own, which a full-sequence forward reproduces."""
    r = cfg.routing
    return dataclasses.replace(cfg, routing=dataclasses.replace(
        r, strategy="topk", capacity_factor=r.n_experts / r.top_k))


def _bf16_ulp(x):
    """Spacing of bfloat16 numbers at |x| (8 significant bits)."""
    import numpy as np

    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def _greedy_parity(rows, got, label: str, tie_ulps: float):
    """Every served token `got[i]` must be the argmax of reference logits
    `rows[i]`, or within `tie_ulps` bf16 ulps (at that row's top logit) of
    it. The logits leave the unembedding in bf16, so two programs that order
    their sums differently can swap a near-tie below that resolution."""
    import numpy as np

    best = rows.max(axis=-1)
    gap = best - rows[np.arange(len(got)), got]
    ulps = gap / _bf16_ulp(best)
    res = {
        "exact_argmax_matches": f"{int(np.sum(rows.argmax(axis=-1) == got))}/{len(got)}",
        "max_logit_gap": float(gap.max()),
        "max_gap_bf16_ulps": float(ulps.max()),
        "top_logit_range": [float(best.min()), float(best.max())],
        "tie_ulps": tie_ulps,
    }
    check(float(ulps.max()) <= tie_ulps, f"{label}: served tokens diverge: {res}")
    return res


def serve_phase(cfg, *, n_requests: int, prompt_lens, gen: int, tie_ulps: float = 2.0):
    """`ContinuousBatchingEngine` at its defaults, then greedy parity.

    All requests must finish with `gen` in-vocabulary tokens. Two parity
    checks follow, each token held to `_greedy_parity`:
      * BIP: one prompt served alone by the config's own model, with a chunk
        that holds the whole prompt, against `prefill_chunk` called directly
        over the same tokens from the same initial router state — the
        engine's scheduling, cache and router-state handling add nothing;
      * forward: the same prompt served by the top-k twin against a plain
        full-sequence forward over prompt + output — chunked, cached serving
        computes what the model computes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [
        rng.integers(0, cfg.vocab_size, (int(rng.integers(*prompt_lens)),)).tolist()
        for _ in range(n_requests)
    ]
    eng = ContinuousBatchingEngine(model, params)
    t0 = time.perf_counter()
    reqs = []
    for p in prompts:
        r = eng.submit(p, gen, ignore_eos=True)
        check(r is not None, "engine refused a request")
        reqs.append(r)
    eng.run()
    wall = time.perf_counter() - t0
    check(all(r.finish_reason == "max_new_tokens" for r in reqs),
          f"finish reasons {[r.finish_reason for r in reqs]}")
    check(all(len(r.output) == gen for r in reqs), "short outputs")
    check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.output),
          "token outside the vocabulary")
    load = eng.expert_load
    out = {
        "arch": cfg.name,
        "dual_solver": dual_solver(cfg),
        "n_slots": eng.n_slots,
        "chunk": eng.chunk_size,
        "requests": len(reqs),
        "prompt_tokens": sum(len(p) for p in prompts),
        "generated_tokens": sum(len(r.output) for r in reqs),
        "engine_steps": eng.n_steps,
        "wall_seconds_incl_compile": wall,
        "expert_load_max_vio": float(load.max() / max(load.mean(), 1e-9) - 1.0),
    }
    eng.close()

    p0 = prompts[0]
    n0 = len(p0)
    eng = ContinuousBatchingEngine(model, params, n_slots=1, chunk_size=n0)
    req = eng.submit(p0, gen, ignore_eos=True)
    eng.run()
    eng.close()
    step = jax.jit(model.prefill_chunk)
    cache = model.init_slot_cache(params, 1, eng.max_seq_len)
    states = model.init_router_states()
    logits, cache, states, _ = step(
        params, jnp.asarray([p0], jnp.int32), cache, states, jnp.asarray([n0], jnp.int32))
    rows = [np.asarray(logits[0, n0 - 1], np.float32)]
    for tok in req.output[:-1]:  # teacher-forced with the served tokens
        chunk = np.zeros((1, n0), np.int32)
        chunk[0, 0] = tok
        logits, cache, states, _ = step(
            params, jnp.asarray(chunk), cache, states, jnp.asarray([1], jnp.int32))
        rows.append(np.asarray(logits[0, 0], np.float32))
    out["parity_bip"] = {
        "gate": "bip (the config's own), one request, chunk = prompt length",
        "reference": "model.prefill_chunk, same tokens and router state",
        "prompt_len": n0,
        **_greedy_parity(np.stack(rows), np.asarray(req.output), "bip", tie_ulps),
    }

    twin = build_model(_topk_twin(cfg))
    eng = ContinuousBatchingEngine(twin, params)
    req = eng.submit(p0, gen, ignore_eos=True)
    eng.run()
    eng.close()
    seq = jnp.asarray([p0 + req.output[:-1]], jnp.int32)
    logits, _, _, _ = jax.jit(twin.forward)(params, {"tokens": seq}, twin.init_router_states())
    out["parity_forward"] = {
        "gate": "topk twin (same params)",
        "reference": "full-sequence forward over prompt + output",
        "prompt_len": n0,
        **_greedy_parity(np.asarray(logits[0, n0 - 1:], np.float32),
                         np.asarray(req.output), "forward", tie_ulps),
    }
    return out


# --------------------------------------------------------------- four chips


def _drop_expert_shard(params, n_shards: int):
    """`params` with the last of `n_shards` expert shards outputting zero.

    This is what an expert exchange that loses one shard's results computes:
    those experts' tokens come back as zeros."""
    import jax

    def zero(path, x):
        if jax.tree_util.keystr(path).endswith("['moe']['w_down']"):
            m = x.shape[-3]
            return x.at[..., m - m // n_shards:, :, :].set(0)
        return x

    return jax.tree_util.tree_map_with_path(zero, params)


def _expert_exchange(cfg, params, x, mesh, n_model: int):
    """Layer 0's MoE through the expert-parallel path `train_loop` takes on
    `mesh`, against the same layer on one device.

    Returns the relative Frobenius gap of the sharded output to the unsharded
    one, and the gap that losing the last expert shard's outputs makes."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import init_router_state
    from repro.distributed.sharding import make_mesh_ctx
    from repro.models import moe

    layer = jax.tree.map(lambda a: a[0], params["stack"]["blocks"][0]["moe"])
    state = init_router_state(moe.router_config(cfg))

    def run(ctx, *args):
        y = jax.jit(lambda p, x, s: moe.moe_ffn(p, x, s, cfg, ctx)[0])(*args)
        return np.asarray(jax.device_get(y), np.float32)

    y_ref = run(None, layer, x, state)
    y_cut = run(None, _drop_expert_shard({"moe": layer}, n_model)["moe"], x, state)
    repl = NamedSharding(mesh, P())
    y_ep = run(make_mesh_ctx(mesh), *jax.device_put((layer, x, state), repl))
    norm = float(np.linalg.norm(y_ref))
    return (float(np.linalg.norm(y_ep - y_ref)) / norm,
            float(np.linalg.norm(y_cut - y_ref)) / norm)


def four_chip_phase(cfg, *, batch: int, seq_len: int, steps: int, micro: int,
                    n_model: int = 4, rel_tol: float = FOUR_CHIP_REL_TOL,
                    moe_rel_tol: float = FOUR_CHIP_MOE_REL_TOL):
    """EP training on a 1 x n_model (data x model) mesh; step-0 loss vs one device.

    The reference runs the same params (same init key as `train_loop`) and
    the same microbatches through the unsharded model on device 0, threading
    the router state from microbatch to microbatch as the train step does.
    A negative control runs the same reference with the last expert shard's
    outputs zeroed (a lost exchange); the bound must sit below that gap, or
    it could not tell a broken expert exchange from a sound one."""
    import jax
    import numpy as np

    from repro.data.synthetic import SyntheticBatchStream
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model

    first = next(iter(SyntheticBatchStream(cfg, batch, seq_len, 1, seed=SEED)))
    ref_model = build_model(cfg)
    size = batch // micro
    mbs = [{k: v[i * size:(i + 1) * size] for k, v in first.items()} for i in range(micro)]
    with jax.default_device(jax.devices()[0]):
        loss_fn = jax.jit(ref_model.loss_fn)

        def step0_loss(params):
            states, losses = ref_model.init_router_states(), []
            for mb in mbs:
                loss, (states, _) = loss_fn(params, mb, states)
                losses.append(float(loss))
            return float(np.mean(losses))

        params = ref_model.init(jax.random.PRNGKey(0))
        ref = step0_loss(params)
        faulted = step0_loss(_drop_expert_shard(params, n_model))
        x = jax.random.normal(jax.random.PRNGKey(SEED), (seq_len, cfg.d_model), cfg.compute_dtype)
    mesh = make_host_mesh(1, n_model)
    moe_rel, moe_fault_rel = _expert_exchange(cfg, params, x, mesh, n_model)
    del params
    out = train_phase(cfg, batch=batch, seq_len=seq_len, steps=steps, micro=micro, mesh=mesh)
    out["mesh"] = dict(mesh.shape)
    out["moe_impl"] = cfg.routing.moe_impl
    out["step0_loss_unsharded_device0"] = ref
    rel = abs(out["losses"][0] - ref) / abs(ref)
    fault_rel = abs(faulted - ref) / abs(ref)
    out["step0_loss_rel_diff"] = rel
    out["step0_loss_one_expert_shard_dropped"] = faulted
    out["fault_rel_diff"] = fault_rel
    out["rel_tol"] = rel_tol
    out["moe_layer0_rel_diff"] = moe_rel
    out["moe_layer0_fault_rel_diff"] = moe_fault_rel
    out["moe_rel_tol"] = moe_rel_tol
    check(rel <= rel_tol, f"EP step-0 loss {out['losses'][0]} vs unsharded {ref}")
    check(moe_fault_rel > moe_rel_tol,
          f"bound {moe_rel_tol} cannot see a dropped expert shard ({moe_fault_rel})")
    check(moe_rel <= moe_rel_tol, f"EP MoE layer off the unsharded one by {moe_rel}")
    return out


# -------------------------------------------------------------------- main


def _full_width(arch: str):
    """A paper config at published widths, bf16 compute, BIP T from the paper.

    remat='block' recomputes each layer in the backward pass, which is what
    fits 8k-token sequences on a 16 GB chip."""
    import jax.numpy as jnp

    from repro import configs

    cfg = configs.get(arch)
    return dataclasses.replace(cfg, compute_dtype=jnp.bfloat16, remat="block")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip expert-parallel minimind-moe-64e phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package (src/repro) is not next to this script",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r} devices", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import setup_compile_cache

    print(f"device_kind={devices[0].device_kind!r} count={len(devices)}")
    print(f"compile cache: {setup_compile_cache()}")
    print(f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
    clock = CompileClock()

    if args.four_chips:
        cfg64 = _full_width("minimind-moe-64e")
        phases = [("four_chips_train_64e_ep", lambda: four_chip_phase(
            cfg64, batch=2, seq_len=cfg64.max_seq_len, steps=3, micro=2))]
    else:
        cfg16 = _full_width("minimind-moe-16e")
        train_cfg = dataclasses.replace(
            cfg16, routing=dataclasses.replace(cfg16.routing, use_kernel=True))
        n_step = 2 * cfg16.max_seq_len
        phases = [
            ("train_16e", lambda: train_phase(
                train_cfg, batch=2, seq_len=cfg16.max_seq_len, steps=5, micro=2)),
            ("kernels_16e", lambda: kernel_phase(
                cfg16, n_tokens=n_step, expect_mosaic=True)),
            ("kernels_64e", lambda: kernel_phase(
                _full_width("minimind-moe-64e"), n_tokens=n_step, expect_mosaic=True)),
            ("serve_16e", lambda: serve_phase(
                cfg16, n_requests=16, prompt_lens=(64, 513), gen=32)),
        ]

    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            res = run()
            status = "ok"
        except Exception as e:  # report every phase, then fail the run
            traceback.print_exc()
            res, status = {"error": f"{type(e).__name__}: {e}"[:2000]}, "FAILED"
            failed.append(name)
        res["phase_seconds"] = time.perf_counter() - t0
        res["compile_seconds"] = clock.take()
        print(f"phase {name} {status}: " + json.dumps(res, default=float))
        sys.stdout.flush()
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
