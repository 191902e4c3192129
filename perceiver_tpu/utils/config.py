"""CLI/config system preserving the reference's LightningCLI surface.

The reference's user contract (SURVEY §5 config): subcommands
``fit``/``validate``/``test``; dotted flags ``--model.*``, ``--data.*``,
``--trainer.*``, ``--optimizer.*``, ``--lr_scheduler.*``;
``--experiment``; datamodule selection by class name (``--data=
IMDBDataModule``); layered defaults (code → trainer defaults YAML →
per-script set_defaults → ``--config`` files → argv); **argument
links** both static (parse-time, e.g. ``trainer.max_steps →
lr_scheduler.init_args.total_steps``) and dynamic (instantiation-time,
e.g. ``data.vocab_size → model.vocab_size``); and a config snapshot
written into the run's log dir (``save_config_overwrite=True``,
``cli.py:22``).

No Lightning/jsonargparse dependency — a small layered-dict parser is
all the semantics require.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import yaml


def _set_dotted(d: dict, key: str, value):
    parts = key.split(".")
    for p in parts[:-1]:
        d = d.setdefault(p, {})
        if not isinstance(d, dict):
            raise ValueError(f"Cannot set {key}: {p} is not a mapping")
    d[parts[-1]] = value


def _get_dotted(d: dict, key: str, default=None):
    for p in key.split("."):
        if not isinstance(d, dict) or p not in d:
            return default
        d = d[p]
    return d


def _deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _parse_value(raw: str):
    try:
        val = yaml.safe_load(raw)
    except yaml.YAMLError:
        return raw
    if isinstance(val, str):
        # YAML 1.1 leaves exponent forms without a decimal point ('1e-4')
        # as strings; CLI users mean the number
        try:
            return int(val)
        except ValueError:
            try:
                return float(val)
            except ValueError:
                return val
    return val


@dataclasses.dataclass
class Link:
    """Argument link: ``apply_on='parse'`` runs on the merged config
    before instantiation; ``apply_on='instantiate'`` reads an attribute
    off the instantiated datamodule (the reference's dynamic links,
    e.g. ``data.image_shape → model.image_shape``, img_clf.py:12-13)."""

    source: str
    target: str
    apply_on: str = "parse"  # "parse" | "instantiate"
    compute_fn: Optional[Callable[[Any], Any]] = None
    # optional gate: the link applies only when this predicate of the
    # merged config holds (e.g. OneCycle-specific links must not inject
    # total_steps/max_lr into a different scheduler class)
    when: Optional[Callable[[dict], bool]] = None


class CLI:
    """Reference-shaped CLI (``scripts/cli.py``): parses argv, layers
    defaults, applies links, instantiates datamodule/task/trainer, runs
    the subcommand, snapshots the effective config."""

    SUBCOMMANDS = ("fit", "validate", "test", "predict")

    def __init__(self, task_cls, datamodules: Dict[str, type],
                 default_datamodule: Optional[str] = None,
                 defaults: Optional[dict] = None,
                 default_config_files: Sequence[str] = (),
                 links: Sequence[Link] = (),
                 description: str = "",
                 run: bool = True,
                 args: Optional[List[str]] = None):
        self.task_cls = task_cls
        self.datamodules = datamodules
        self.default_datamodule = default_datamodule
        self.links = list(links)
        self.description = description

        argv = list(sys.argv[1:] if args is None else args)
        if argv and argv[0] in ("-h", "--help"):
            self._print_help()
            sys.exit(0)
        if not argv or argv[0] not in self.SUBCOMMANDS:
            raise SystemExit(
                f"usage: {sys.argv[0]} {{{','.join(self.SUBCOMMANDS)}}} "
                f"[--key=value ...]  (see --help)")
        self.subcommand = argv[0]

        config: dict = {}
        for path in default_config_files:
            if os.path.exists(path):
                with open(path) as f:
                    config = _deep_merge(config, yaml.safe_load(f) or {})
        if defaults:
            flat = {}
            for k, v in defaults.items():
                _set_dotted(flat, k, v)
            config = _deep_merge(config, flat)
        # 'defaulted' marks a scheduler a script's DEFAULTS inject
        # (mlm.py's always-on OneCycleLR): consumed here, before the
        # user's explicit config merges — a user-supplied 'defaulted'
        # key survives into the optimizer factory, which rejects it as
        # unknown. The resolved flag travels out-of-band (a Trainer
        # argument), never through config, so snapshots and the
        # checkpoint hparams stay clean.
        sched_defaulted = bool(
            isinstance(config.get("lr_scheduler"), dict)
            and config["lr_scheduler"].pop("defaulted", False))

        # --config file contents and dotted flags merge last-wins in
        # argv order (reference LightningCLI/jsonargparse semantics:
        # `--lr=x --config b.yaml` yields b.yaml's value, while
        # `--config b.yaml --lr=x` yields x)
        explicit: dict = {}
        i = 1
        while i < len(argv):
            arg = argv[i]
            if not arg.startswith("--"):
                raise SystemExit(f"Unexpected argument: {arg}")
            if arg == "--print_config" or arg.startswith("--print_config="):
                # valueless, `=v`, and space-separated forms all work
                if "=" in arg:
                    val = _parse_value(arg.split("=", 1)[1])
                    i += 1
                elif i + 1 < len(argv) and not argv[i + 1].startswith("--"):
                    val = _parse_value(argv[i + 1])
                    i += 2
                else:
                    val = True
                    i += 1
                self._print_config_requested = bool(val)
                continue
            if "=" in arg:
                key, raw = arg[2:].split("=", 1)
                i += 1
            else:
                key = arg[2:]
                if i + 1 >= len(argv):
                    raise SystemExit(f"--{key} requires a value")
                raw = argv[i + 1]
                i += 2
            if key == "config":
                with open(raw) as f:
                    explicit = _deep_merge(explicit,
                                           yaml.safe_load(f) or {})
            else:
                val = _parse_value(raw)
                if key == "data" and isinstance(val, str):
                    # --data=IMDBDataModule selection composes with
                    # --data.* option flags (reference README.md:36)
                    key, val = "data.class_name", val
                _set_dotted(explicit, key, val)
        # everything the user stated explicitly — via --config file or
        # dotted flag — overrides defaults and suppresses parse-time
        # links equally
        config = _deep_merge(config, explicit)

        # a scheduler counts as defaulted only while the user hasn't
        # configured the group themselves
        self._sched_defaulted = (sched_defaulted
                                 and "lr_scheduler" not in explicit)

        # static (parse-time) links — a link only fills values into a
        # group the user actually configured (linking OneCycle args into
        # an absent lr_scheduler would fabricate a broken scheduler)
        for link in self.links:
            if link.apply_on != "parse":
                continue
            if link.when is not None and not link.when(config):
                continue
            target_root = link.target.split(".")[0]
            if target_root not in config:
                continue
            val = _get_dotted(config, link.source)
            if val is not None and _get_dotted(
                    explicit, link.target) is None:
                if link.compute_fn:
                    val = link.compute_fn(val)
                _set_dotted(config, link.target, val)

        self.config = config
        if getattr(self, "_print_config_requested", False):
            yaml.safe_dump(config, sys.stdout, sort_keys=True)
            sys.exit(0)
        if run:
            self.run()

    # --- instantiation -------------------------------------------------------

    def _field_names(self, cls) -> set:
        return {f.name for f in dataclasses.fields(cls)}

    def instantiate(self) -> Tuple[Any, Any, Any]:
        from perceiver_tpu.training import Trainer, TrainerConfig

        raw_data = self.config.get("data", {}) or {}
        if isinstance(raw_data, str):  # config-file form: `data: Name`
            dm_name, data_cfg = raw_data, {}
        else:
            data_cfg = dict(raw_data)
            dm_name = data_cfg.pop("class_name", None) \
                or self.config.get("data_class") or self.default_datamodule
        if dm_name not in self.datamodules:
            raise SystemExit(
                f"Unknown datamodule {dm_name!r}; choices: "
                f"{sorted(self.datamodules)}")
        datamodule = self.datamodules[dm_name](**data_cfg)

        # dynamic links: datamodule attribute → model config
        model_cfg = dict(self.config.get("model", {}) or {})
        for link in self.links:
            if link.apply_on != "instantiate":
                continue
            src_attr = link.source.split(".", 1)[1]
            val = getattr(datamodule, src_attr, None)
            if val is not None:
                if link.compute_fn:
                    val = link.compute_fn(val)
                model_cfg.setdefault(link.target.split(".", 1)[1], val)

        allowed = self._field_names(self.task_cls)
        unknown = set(model_cfg) - allowed
        if unknown:
            raise SystemExit(f"Unknown --model args: {sorted(unknown)}")
        # tuples where dataclasses expect them
        for k, v in model_cfg.items():
            if isinstance(v, list):
                model_cfg[k] = tuple(v)
        task = self.task_cls(**model_cfg)

        trainer_cfg = dict(self.config.get("trainer", {}) or {})
        if "experiment" in self.config:
            trainer_cfg.setdefault("experiment",
                                   self.config["experiment"])
        t_allowed = self._field_names(TrainerConfig)
        t_unknown = set(trainer_cfg) - t_allowed
        if t_unknown:
            raise SystemExit(f"Unknown --trainer args: {sorted(t_unknown)}")
        tcfg = TrainerConfig(**trainer_cfg)

        scheduler_init = self.config.get("lr_scheduler")
        sched_defaulted = getattr(self, "_sched_defaulted", False)
        if scheduler_init is not None and sched_defaulted \
                and self.subcommand != "fit":
            # validate/test/predict never step the optimizer — a
            # default-injected schedule (and its possible warning) has
            # no business there
            scheduler_init = None

        trainer = Trainer(
            task, datamodule, tcfg,
            optimizer_init=self.config.get("optimizer"),
            scheduler_init=scheduler_init,
            scheduler_defaulted=sched_defaulted,
            mesh=self._build_mesh(trainer_cfg))
        return task, datamodule, trainer

    def _build_mesh(self, trainer_cfg: dict):
        import jax

        # platform selection must precede the first jax.devices() call
        # (it initializes the backend for the whole process)
        from perceiver_tpu.training.trainer import apply_accelerator
        apply_accelerator(trainer_cfg.get("accelerator", "auto"))
        mp = int(trainer_cfg.get("model_parallel", 1) or 1)
        sp = int(trainer_cfg.get("seq_parallel", 1) or 1)
        # --trainer.devices=N uses the first N devices (reference
        # README.md:43 semantics); "auto"/-1 → all visible devices.
        # Anything else fails loudly — silently dropping a device
        # constraint would change per-device batch sizes unnoticed.
        dev = trainer_cfg.get("devices", "auto")
        if isinstance(dev, str) and dev.lstrip("-").isdigit():
            dev = int(dev)
        n = None
        if isinstance(dev, bool) or not (
                dev in ("auto", -1, None) or
                (isinstance(dev, int) and dev > 0)):
            raise SystemExit(
                f"--trainer.devices={dev!r} not supported: use an int "
                "count, -1, or auto (device *lists* are not supported; "
                "the mesh always takes the first N devices)")
        if isinstance(dev, int) and dev > 0:
            n = dev
            if jax.process_count() > 1:
                raise SystemExit(
                    "--trainer.devices=N is single-host only (a global "
                    "mesh over the first N devices would exclude other "
                    "hosts' chips); on pods, control topology via the "
                    "TPU runtime / jax.distributed instead")
        if (n or len(jax.devices())) <= 1 and mp * sp <= 1:
            return None
        from perceiver_tpu.parallel import make_mesh
        return make_mesh(n, model_parallel=mp, seq_parallel=sp)

    # --- run -----------------------------------------------------------------

    def run(self):
        from perceiver_tpu.cache import enable_compile_cache

        enable_compile_cache()
        # predict preconditions fail before any heavy work (dataset
        # prep, param init): it needs a task with a predict path and a
        # trained checkpoint — random-init "predictions" would be
        # garbage indistinguishable from real output
        if self.subcommand == "predict":
            if not hasattr(self.task_cls, "predict"):
                raise SystemExit(
                    f"{self.task_cls.__name__} has no predict path "
                    "(only the MLM task does)")
            if not self.config.get("ckpt_path") and \
                    not (self.config.get("model") or {}).get("torch_ckpt"):
                raise SystemExit(
                    "predict requires --ckpt_path=<trained checkpoint> "
                    "(or --model.torch_ckpt=<reference checkpoint>)")
            if not (self.config.get("model") or {}).get("masked_samples"):
                raise SystemExit(
                    "predict requires --model.masked_samples")
        task, datamodule, trainer = self.instantiate()
        self.trainer = trainer
        # config snapshot BEFORE running (reference cli.py:22
        # SaveConfigCallback writes at setup): a preempted / killed /
        # still-running fit must still leave its config.yaml — any
        # post-mortem reads it from the version dir
        os.makedirs(trainer.log_dir, exist_ok=True)
        with open(os.path.join(trainer.log_dir, "config.yaml"), "w") as f:
            yaml.safe_dump(self.config, f, sort_keys=True)
        if self.subcommand == "fit":
            state = trainer.fit()
        else:
            trainer._prepare_data()
            trainer.datamodule.setup()
            state = trainer._build_state()
            if self.config.get("ckpt_path"):
                from perceiver_tpu.training.checkpoint import restore_params
                params = restore_params(self.config["ckpt_path"],
                                        template=state.params)
                state = dataclasses.replace(state, params=params)
            if self.subcommand == "validate":
                result = trainer.validate(state)
            elif self.subcommand == "test":
                result = trainer.test(state)
            else:  # predict — the reference's only inference entry
                # (masked-sample top-k fills, SURVEY §3.5)
                result = trainer.task.predict(trainer, state)
            print(yaml.safe_dump(result, sort_keys=True,
                                 allow_unicode=True))
        return state if self.subcommand == "fit" else result

    def _print_help(self):
        print(self.description or "perceiver_tpu CLI")
        print(f"\nusage: {sys.argv[0]} {{{','.join(self.SUBCOMMANDS)}}} "
              "[--key=value ...]\n")
        print("flag groups: --model.* --data.* --trainer.* --optimizer.* "
              "--lr_scheduler.* --experiment NAME --config FILE "
              "--print_config")
        print(f"\ndatamodules: {sorted(self.datamodules)}")
        print("\nmodel flags:")
        for f in dataclasses.fields(self.task_cls):
            print(f"  --model.{f.name} (default {f.default!r})")
