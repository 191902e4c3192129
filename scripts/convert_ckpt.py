#!/usr/bin/env python
"""Convert checkpoints between this framework and the reference's
torch format, both directions — the standalone companion to the
in-training ``--model.torch_ckpt`` flags.

    # reference .ckpt / run.py save → an orbax params dir usable with
    # --ckpt_path / --model.mlm_ckpt / --model.clf_ckpt
    python scripts/convert_ckpt.py from-torch ref_mlm.ckpt logs/imported

    # a trained orbax checkpoint → a torch state-dict .ckpt a
    # reference user can load_state_dict into their model
    python scripts/convert_ckpt.py to-torch \\
        logs/mlm/version_0/checkpoints out.ckpt [--sequential]

``from-torch`` needs no model config — structure comes from the
checkpoint itself. ``to-torch --sequential`` emits the ``0.``/``1.``
child names of the reference's Sequential ``PerceiverIO`` (classifier
and ``run.py`` models; reference ``model.py:321-325``) instead of the
named ``encoder.``/``decoder.`` form of ``PerceiverMLM``.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    # conversion is pure host-side work, but orbax pulls in jax, which
    # would otherwise take the chip — force CPU before any restore/save
    import jax

    jax.config.update("jax_platforms", "cpu")

    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    ft = sub.add_parser("from-torch",
                        help="torch .ckpt → orbax params directory")
    ft.add_argument("src")
    ft.add_argument("out")
    tt = sub.add_parser("to-torch",
                        help="orbax checkpoint → torch .ckpt")
    tt.add_argument("src")
    tt.add_argument("out")
    tt.add_argument("--sequential", action="store_true",
                    help="emit PerceiverIO Sequential child names (0/1)")
    args = ap.parse_args()

    if args.cmd == "from-torch":
        from perceiver_tpu.training.checkpoint import save_params
        from perceiver_tpu.utils.torch_import import restore_from_torch

        params = restore_from_torch(args.src)
        save_params(args.out, params)
        n = sum(1 for _ in _leaves(params))
        print(f"imported {n} arrays from {args.src} -> {args.out}")
    else:
        import torch

        from perceiver_tpu.training.checkpoint import restore_params
        from perceiver_tpu.utils.torch_import import (
            export_perceiver_params,
        )

        params = restore_params(args.src)
        sd = export_perceiver_params(params, sequential=args.sequential)
        torch.save({"state_dict": {k: torch.as_tensor(v).clone()
                                   for k, v in sd.items()}}, args.out)
        print(f"exported {len(sd)} tensors from {args.src} -> {args.out}")


def _leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    else:
        yield tree


if __name__ == "__main__":
    main()
