"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke]``.

End-to-end: synthetic corpus -> Trainer (AdamW, schedule, checkpoints,
restart) -> held-out perplexity.  ``--smoke`` uses the reduced config
(CPU-friendly); full configs expect accelerators and the sharded step
from distributed/train.py (enabled with --mesh).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.base import ALL_ARCHS
from repro.data import CorpusConfig, MarkovCorpus
from repro.launch import enable_compile_cache
from repro.models.registry import load_arch
from repro.train import AdamWConfig, TrainConfig, Trainer, evaluate_ppl
from repro.utils import get_logger

log = get_logger("launch.train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt125m-proxy",
                    choices=ALL_ARCHS + ["opt125m-proxy"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd", "const"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    model = load_arch(args.arch, smoke=args.smoke)
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=args.seed))
    extras_fn = None
    if model.cfg.family in ("vlm", "encdec"):
        proto = model.make_batch(jax.random.PRNGKey(0), args.batch, args.seq)
        extra = {k: v for k, v in proto.items() if k not in ("tokens", "labels")}
        extras_fn = lambda b: {k: v[:b] for k, v in extra.items()}

    cfg = TrainConfig(
        steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, seed=args.seed,
        ckpt_extra={"arch": args.arch, "smoke": args.smoke,
                    "corpus_seed": args.seed},
        optim=AdamWConfig(lr=args.lr, schedule=args.schedule,
                          warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps))
    tr = Trainer(model, corpus, cfg, extras_fn=extras_fn)
    if args.resume and tr.restore():
        log.info("resuming at step %d", tr.step)
    out = tr.run()
    ppl = evaluate_ppl(model, tr.params, corpus, args.batch, args.seq, 4,
                       extras=extras_fn(args.batch) if extras_fn else None)
    loss_s = "n/a" if out["final_loss"] is None else f"{out['final_loss']:.4f}"
    print(f"arch={args.arch} steps={out['steps']} final_loss={loss_s} "
          f"valid_ppl={ppl:.3f} wall={out['wall_seconds']:.1f}s")


if __name__ == "__main__":
    main()
