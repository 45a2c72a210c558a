"""Training launcher.

Smoke-scale on this CPU container (``--smoke``), production lowering via
``--dry-run`` (which defers to ``launch.dryrun``), and real-device runs on
a TPU slice (same code path, jax picks up the TPU topology).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 50 --workdir /tmp/run1
"""

from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs import ARCH_IDS, canonical, get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {ARCH_IDS} (dashed aliases ok)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config runnable on CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the (16,16) mesh (needs 256 devices)")
    args = ap.parse_args()

    use_compile_cache()
    arch = canonical(args.arch)
    cfg = get_smoke_config(arch) if args.smoke else get_config(arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())

    dcfg = DataConfig(batch=args.batch, seq=args.seq,
                      vocab_size=cfg.vocab_size)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                       total_steps=args.steps)
    tcfg = TrainerConfig(workdir=args.workdir, total_steps=args.steps,
                         ckpt_every=args.ckpt_every,
                         grad_accum=args.grad_accum,
                         compression=args.compression, fsdp=args.fsdp)
    os.makedirs(args.workdir, exist_ok=True)
    trainer = Trainer(cfg, dcfg, ocfg, tcfg, mesh)
    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) on "
          f"{len(jax.devices())} device(s), resuming from step "
          f"{trainer.step}")
    log = trainer.run()
    out = os.path.join(args.workdir, "metrics.json")
    with open(out, "w") as f:
        json.dump(log, f, indent=1)
    print(f"final loss {log[-1]['loss']:.4f} "
          f"({log[-1]['step_time']*1e3:.0f} ms/step); metrics -> {out}")


if __name__ == "__main__":
    main()
