"""FARSI as the framework's auto-configuration engine (DESIGN.md §2): explore
the distributed-execution design space of an (arch × shape) cell on the
production mesh, printing each hypothesis → measurement cycle.

  PYTHONPATH=src python examples/autotune_sharding.py --arch qwen3-1.7b --shape train_4k
"""
import argparse

from repro.configs.base import SHAPES
from repro.configs.registry import arch_names, get_config
from repro.launch.autotune import autotune
from repro.roofline.analytic import MeshShape, model_flops
from repro.runtime.compile_cache import use_compile_cache
from repro.sharding.rules import DistConfig


def baseline_rules():
    return {
        "qkv": ("model",), "kv_qkv": ("model",), "mlp": ("model",),
        "ssm_inner": ("model",), "ssm_conv": ("model",), "expert_mlp": ("model",),
        "seq_res": ("model",), "embed": ("data",),
    }


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=arch_names(), default="qwen3-1.7b")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    ap.add_argument("--iterations", type=int, default=30)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = MeshShape(16, 16)
    micro = 8 if cfg.param_counts()["total"] >= 50e9 else 4
    d0 = DistConfig(rules=baseline_rules(), microbatches=micro)

    res = autotune(cfg, shape, mesh, d0, iterations=args.iterations)
    b, a = res.baseline_terms, res.best_terms
    print(f"{args.arch} × {args.shape} on 16×16 (256 chips)\n")
    print(f"{'':12s}{'baseline':>14s}{'tuned':>14s}")
    for k, label in [("t_compute_s", "compute"), ("t_memory_s", "HBM"),
                     ("t_collective_s", "ICI"), ("t_phase_sim_s", "step est")]:
        print(f"{label:12s}{b[k]*1e3:12.1f}ms{a[k]*1e3:12.1f}ms")
    print(f"{'HBM state':12s}{b['hbm_state_bytes']/1e9:12.1f}GB{a['hbm_state_bytes']/1e9:12.1f}GB")
    speedup = b["t_phase_sim_s"] / a["t_phase_sim_s"]
    mf = model_flops(cfg, shape) / mesh.chips
    frac_b = mf / 197e12 / b["t_phase_sim_s"] * 100
    frac_a = mf / 197e12 / a["t_phase_sim_s"] * 100
    print(f"\nestimated speedup: {speedup:.2f}x   roofline fraction: {frac_b:.1f}% → {frac_a:.1f}%")
    print(f"tuned config: microbatches={res.best.microbatches} remat={res.best.remat} "
          f"attn={res.best.attn_impl} tp={'on' if res.best.rules.get('qkv') else 'off'} "
          f"sp={'on' if res.best.rules.get('seq_res') else 'off'}\n")
    print("hypothesis → measurement log:")
    for r in res.log:
        mark = "✓" if r.accepted else "✗"
        print(f" {mark} it{r.iteration:02d} {r.move}:{r.knob:14s} "
              f"{r.before['t_phase_sim_s']*1e3:9.1f} → {r.after['t_phase_sim_s']*1e3:9.1f} ms | {r.hypothesis}")


if __name__ == "__main__":
    main()
