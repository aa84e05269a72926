"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

``main`` gives the CPU backend 512 devices before JAX initialises (the
device count locks at first init), so run it on the host:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen2.5-3b --shape train_4k --mesh single --out results.jsonl

or with --all to sweep every live cell sequentially.  Each cell prints
``memory_analysis()`` (proof it fits) and ``cost_analysis()`` FLOPs/bytes
(roofline inputs), and appends a JSON record.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.distributed.api import activation_sharding
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        default_rules, make_act_resolver,
                                        param_shardings,
                                        train_state_shardings)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, use_host_devices
from repro.models.config import SHAPES, shape_applicable
from repro.models.registry import build_model
from repro.optim.adamw import AdamW, warmup_cosine
from repro.roofline import analysis as roofline
from repro.train.train_step import (StepConfig, abstract_train_state,
                                    make_train_step)


# Per-(arch, shape) step-config overrides: microbatches bound the live
# activation footprint; loss_chunks bound the (tokens, vocab) logits buffer.
def step_config_for(arch_name: str, shape_name: str,
                    overrides=None) -> StepConfig:
    big = arch_name in ("deepseek-v2-236b", "command-r-plus-104b",
                        "internvl2-76b", "llama4-scout-17b-a16e")
    cfg = dict(
        remat="nothing_saveable",
        microbatches=8 if big else 2,
        loss_chunks=8,
        kv_chunk=2048,
    )
    if overrides:
        cfg.update(overrides)
    return StepConfig(**cfg)


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               step_overrides=None, rules_overrides=None,
               verbose: bool = True):
    arch = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = default_rules(multi_pod=multi_pod)
    if rules_overrides:
        rules = rules.replace(**rules_overrides)
    model = build_model(arch)
    resolver = make_act_resolver(mesh, rules)

    t0 = time.time()
    with mesh:
        with activation_sharding(resolver):
            if shape.kind == "train":
                scfg = step_config_for(arch_name, shape_name, step_overrides)
                optimizer = AdamW(lr=warmup_cosine(3e-4, 2000, 100000))
                step = make_train_step(model, optimizer, scfg)
                state_abs = abstract_train_state(model, optimizer)
                state_sh = train_state_shardings(mesh, rules, model.specs(),
                                                 state_abs)
                batch_abs = model.input_specs(shape)
                b_sh = batch_shardings(mesh, rules, batch_abs)
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, b_sh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,),
                ).lower(state_abs, batch_abs)
                tokens = shape.global_batch * shape.seq_len
                mflops = roofline.model_flops_train(
                    model.active_param_count(), tokens)
            elif shape.kind == "prefill":
                batch_abs = model.input_specs(shape)
                b_sh = batch_shardings(mesh, rules, batch_abs)
                params_abs = model.abstract()
                p_sh = param_shardings(mesh, rules, model.specs(), params_abs)

                def serve_prefill(params, batch):
                    return model.prefill(params, batch,
                                         max_seq=shape.seq_len)

                lowered = jax.jit(
                    serve_prefill, in_shardings=(p_sh, b_sh),
                ).lower(params_abs, batch_abs)
                tokens = shape.global_batch * shape.seq_len
                mflops = roofline.model_flops_decode(
                    model.active_param_count(), tokens)
            else:  # decode
                batch_abs = model.input_specs(shape)
                b_sh = batch_shardings(mesh, rules, batch_abs)
                params_abs = model.abstract()
                p_sh = param_shardings(mesh, rules, model.specs(), params_abs)
                cache_abs = model.cache_specs(shape.global_batch,
                                              shape.seq_len)
                c_sh = cache_shardings(mesh, rules, cache_abs,
                                       shape.global_batch, shape.seq_len)

                def serve_step(params, cache, batch):
                    return model.decode(params, cache, batch)

                lowered = jax.jit(
                    serve_step, in_shardings=(p_sh, c_sh, b_sh),
                    donate_argnums=(1,),
                ).lower(params_abs, cache_abs, batch_abs)
                tokens = shape.global_batch
                mflops = roofline.model_flops_decode(
                    model.active_param_count(), tokens)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    rf = roofline.analyze_compiled(compiled, chips=chips,
                                   model_flops=mflops)
    rec = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "roofline": rf.summary(),
    }
    if verbose:
        print(f"[dryrun] {arch_name} x {shape_name} x "
              f"{'multi' if multi_pod else 'single'}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: args={rec['memory']['argument_bytes']/2**30:.2f}GiB "
              f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
              f"(per device)")
        print(f"  cost_analysis: flops={rf.flops:.3e} bytes={rf.hbm_bytes:.3e} "
              f"coll={rf.collective_bytes:.3e}B")
        print(f"  roofline: compute={rf.compute_s*1e3:.2f}ms "
              f"memory={rf.memory_s*1e3:.2f}ms "
              f"collective={rf.collective_s*1e3:.2f}ms "
              f"-> {rf.dominant}-bound; useful={rf.useful_flops_ratio:.2f}")
    return rec


def main(argv=None):
    use_host_devices(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cells = []
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                for mp in meshes:
                    cells.append((arch, shape, mp))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --all")
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    out = open(args.out, "a") if args.out else None
    failures = 0
    for arch, shape, mp in cells:
        try:
            rec = lower_cell(arch, shape, mp)
        except Exception as e:  # noqa: BLE001 — record and continue
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape,
                   "mesh": "multi" if mp else "single",
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()
    if out:
        out.close()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
