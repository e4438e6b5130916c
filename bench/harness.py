"""One run of one cell: set-up, the measured window, the trace, the check.

The window drives the program's own training loop, ``Trainer.train_epoch``,
on a ``Trainer`` built as ``repro.launch.train.main`` builds one.  The
harness adds only a wrapper around the loader's step iterator (``Feed``),
which counts what each step hands to the trainer and ends the pass at a step
count or a deadline, and, during set-up, a probe around the jitted step that
reads what the comparison needs from its outputs.

Set-up (``setup_s``, from process start to the window's first step):

  1. build the loader, the model and the trainer; enumerate on the host the
     step shapes of the epoch the window runs in (same loader, same seed);
  2. make the train state on the device in one jitted call from the seed;
  3. warm every step shape through the trainer's jitted step with
     ``lower``/``compile`` (persistent compile cache), and run the host
     side of one step of each shape, whose small eager programs compile;
  4. run the epoch's first three steps through ``train_epoch``, reading the
     loss of each, the first gradient from the optimizer's state, and the
     parameters' change after the third.

The window then continues the same epoch through ``train_epoch`` until
``seconds`` have passed, and closes on ``block_until_ready`` of the state it
returns.  After it: the device's peak memory is read, the loader's epoch is
closed (its DGAP audit drains), the program's state is freed and the plain
reference that the configuration names (``bench/references/<name>.py``)
follows the three steps for the comparison.

The profiler trace of a ``--trace 1`` run is reduced with the kernel
families that the configuration's flops module declares (``KERNELS``), and
the work of each family per step (``kernel_work``).
"""

from __future__ import annotations

import gc
import json
import pathlib
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import cell as cells
import check
import xplane

PEAKS = cells.CODE_DIR / "peaks.json"
TRACE_DIR = ".bench_trace"  # under the checkout, git-ignored
MOSAIC = r'custom_call_target="tpu_custom_call"'  # a Pallas kernel's call
TRAIN_PROGRAM = r"train_step"
TRACE_FROM = 2 / 3  # the profiler covers the window's last third


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Feed:
    """Wraps the loader's step iterator.

    Installed on the loader in place of ``streaming_epoch``: the trainer's
    first call opens the program's own iterator, and every call returns a
    pass over that one iterator, so the set-up steps and the window are one
    epoch.  Each pass ends after ``max_steps`` steps or at ``deadline``.
    """

    def __init__(self, loader):
        self._open = loader.streaming_epoch
        self._inner = None
        loader.streaming_epoch = self._epoch
        self.start_pass()

    def _epoch(self, epoch=0, **kw):
        if self._inner is None:
            self._inner = self._open(epoch, **kw)
        return self._pass()

    def start_pass(self, *, max_steps=None, deadline=None, keep=False, before_pull=None):
        """``before_pull`` is called with the pass's step count before each
        pull; ``keep`` keeps each LoaderStep of the pass in ``kept``."""
        self.max_steps, self.deadline = max_steps, deadline
        self.keep, self.before_pull = keep, before_pull
        self.kept = []
        self.records = []  # (real tokens, samples, device tokens) per step of the pass

    def _pass(self):
        while True:
            n = len(self.records)
            if self.max_steps is not None and n >= self.max_steps:
                return
            if self.deadline is not None and time.perf_counter() >= self.deadline:
                return
            if self.before_pull is not None:
                self.before_pull(n)
            with jax.profiler.TraceAnnotation("bench/pull"):
                step = next(self._inner, None)
            if step is None:
                return
            self.records.append(
                (step.metadata.total_tokens, step.metadata.emitted_samples, step.device_tokens)
            )
            if self.keep:
                self.kept.append(step)
            yield step

    def close(self):
        if self._inner is not None:
            self._inner.close()


def global_arrays(step) -> dict:
    """The step's per-rank batches stacked into the global arrays."""
    keys = ("tokens", "positions", "segments", "loss_mask")
    return {k: np.concatenate([getattr(b, k) for b in step.batches]) for k in keys}


def step_shape(step) -> tuple[int, int]:
    rows = sum(b.shape[0] for b in step.batches)
    return rows, step.batches[0].shape[1]


def segment_lengths(step) -> list[int]:
    """Lengths of the documents of one step."""
    out = []
    for b in step.batches:
        for row in b.segments:
            ids = row[row > 0]
            if ids.size:
                cuts = np.flatnonzero(np.diff(ids)) + 1
                out += [len(x) for x in np.split(ids, cuts)]
    return out


# Groups of a layer whose leaves the comparison names by their own key (the
# mixer's ``wq``, the dense MLP's ``w_in``), and the names of its norms.
_FLAT_GROUPS = ("mixer", "mlp")
_NORM_NAMES = {"norm_mixer": "attn_norm", "norm_ffn": "mlp_norm"}


def _key(k):
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return getattr(k, attr)
    return str(k)


def _inner_name(keys) -> str:
    if len(keys) == 2 and keys[1] == "scale":  # a norm: by the part it comes before
        return _NORM_NAMES.get(keys[0], keys[0])
    if keys[0] in _FLAT_GROUPS:
        keys = keys[1:]
    return ".".join(str(k) for k in keys)


def leaf_names(path, plan) -> list[str]:
    """The comparison's names for a leaf of the program's parameter tree
    (``reference.py`` names the reference's alike): one name, or one per
    unit for a leaf of the scanned stack, whose first axis runs over units.

    A layer's leaf is ``layerNN.<path inside the layer>``, the layer's index
    in the model from ``plan`` (``models.blocks.stack_plan``): the unrolled
    prefix's layer ``i`` is ``plan.prefix_layers[i]``, unit ``u``'s
    sub-layer ``j`` is ``plan.unit_layers[u][j]``.
    """
    keys = [_key(k) for k in path]
    if keys[0] == "prefix":  # prefix, i, sub0, ...
        return [f"layer{plan.prefix_layers[keys[1]]:02d}.{_inner_name(keys[3:])}"]
    if keys[0] == "stack":  # stack, sub<j>, ...
        j = int(keys[1].removeprefix("sub"))
        return [f"layer{unit[j]:02d}.{_inner_name(keys[2:])}" for unit in plan.unit_layers]
    return [_inner_name(keys)]


def leaf_norms(tree, plan, minus=None) -> dict:
    """Norm of each leaf of the program's parameter-shaped ``tree`` (of
    ``tree - minus``), each layer apart, by ``leaf_names``."""

    def norms(t, m):
        diff = t if m is None else jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), t, m)
        out = {}
        for path, x in jax.tree_util.tree_flatten_with_path(diff)[0]:
            sq = jnp.square(x.astype(jnp.float32))
            if _key(path[0]) == "stack":
                out[jax.tree_util.keystr(path)] = jnp.sqrt(jnp.sum(sq.reshape(sq.shape[0], -1), axis=1))
            else:
                out[jax.tree_util.keystr(path)] = jnp.sqrt(jnp.sum(sq))
        return out

    got = jax.device_get(jax.jit(norms)(tree, minus))
    flat = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        for name, x in zip(leaf_names(path, plan), np.atleast_1d(got[jax.tree_util.keystr(path)])):
            if name in flat:
                raise ValueError(f"two leaves of the program's tree are named {name!r}")
            flat[name] = float(x)
    return flat


class Probe:
    """Reads the first three steps' outputs around the trainer's jitted step."""

    def __init__(self, trainer, key, beta1: float):
        from repro.models.blocks import stack_plan

        self.trainer, self.key, self.beta1 = trainer, key, beta1
        self.plan = stack_plan(trainer.model.cfg)
        self.step = trainer._train_step
        self.losses = []
        self.grad1 = self.change = None
        self._init = jax.jit(trainer.model.init)
        trainer._train_step = self

    def __call__(self, state, batch):
        new_state, metrics = self.step(state, batch)
        self.losses.append(metrics["loss"])
        if len(self.losses) == 1:
            m = leaf_norms(new_state["opt"]["m"], self.plan)
            self.grad1 = {k: v / (1.0 - self.beta1) for k, v in m.items()}
        if len(self.losses) == 3:
            start = self._init(self.key)
            self.change = leaf_norms(new_state["params"], self.plan, start)
            del start
        return new_state, metrics

    def remove(self) -> dict:
        self.trainer._train_step = self.step
        return {"loss": [float(x) for x in self.losses], "grad1": self.grad1, "change": self.change}


def peaks(kind: str) -> dict:
    """The chip's published peaks, by JAX's ``device_kind``."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}; have {sorted(table)}")
    return table[kind]


def open_device(cell, require_tpu: bool = True):
    """The devices, after the compile cache is placed; raises ``NoChip``
    where the cell's TPU chips are not there."""
    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"the cell needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform!r} device(s)"
        )
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")
    return devices


def build_trainer(cell):
    """The loader and the trainer, as the training launcher builds them."""
    from repro.models import LM
    from repro.train.trainer import Trainer, TrainerConfig

    arch = cells.arch_config(cell.config, cell.root)
    loader = cells.make_loader(cell.traffic, arch.vocab_size)
    opt = cells.optimizer_config(cell.config)
    trainer = Trainer(LM(arch), loader, opt, TrainerConfig(checkpoint_every=20, log_every=5))
    trainer._build_step()
    return trainer, loader


def epoch_shapes(cell, vocab_size: int) -> dict:
    """One step of each distinct shape of the epoch the window runs in, by a
    host-only pass of a loader built the same way."""
    shadow = cells.make_loader(cell.traffic, vocab_size)
    examples = {}
    for st in shadow.streaming_epoch(0):
        examples.setdefault(step_shape(st), st)
    return examples


def first_steps(trainer, loader, key, state):
    """The epoch's first three steps through ``train_epoch``, with the probe
    on; returns the state, the step count, the feed, the program's readings
    and the three steps' arrays."""
    feed = Feed(loader)
    probe = Probe(trainer, key, trainer.opt_cfg.betas[0])
    feed.start_pass(max_steps=3, keep=True)
    state, step_idx = trainer.train_epoch(state, epoch=0, start_step=0)
    program = probe.remove()
    probed = [(global_arrays(s), s.metadata) for s in feed.kept]
    return state, step_idx, feed, program, probed


def verdict(cell, seed: int, program: dict, probed, audit, packed: bool, **ref_kw) -> dict:
    """The numbers compared with the plain reference, each beside its limit,
    and the reference's readings."""
    batches = [{k: a[k] for k in ("tokens", "segments", "loss_mask")} for a, _ in probed]
    ref = cell.module("references", cell.config["reference"]).train3(cell.config, seed, batches, **ref_kw)
    layout_bad = sum(check.layout_violations(a, md, packed) for a, md in probed)
    eta = float(audit.eta_identity + audit.eta_quota)
    compared = check.compare(program, ref, layout_bad, eta, cell.limits)
    log(f"losses {program['loss']} reference {ref['loss']}")
    return compared, ref


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float, root: pathlib.Path,
        require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns what the result line is made of."""
    from repro import obs
    from repro.train.trainer import assemble_model_batch

    devices = open_device(cell, require_tpu)
    dev = devices[0]

    # 1. loader, model, trainer; the epoch's step shapes.
    trainer, loader = build_trainer(cell)
    examples = epoch_shapes(cell, trainer.model.cfg.vocab_size)

    # 2. state from the seed, on the device, in one call.
    key = jax.random.PRNGKey(seed)
    state = jax.jit(trainer.init_state)(key)

    # 3. warm every shape: the jitted step and the host side of one step.
    compiles = obs.counter("train_compile_events_total")
    realize = obs.counter("train_realize_seconds_total")
    took = []
    for shape, st in sorted(examples.items()):
        t = time.perf_counter()
        batch = assemble_model_batch(st, loader.layout)
        trainer._train_step.lower(state, batch).compile()
        took.append(f"{shape[0]}x{shape[1]}:{time.perf_counter() - t:.1f}s")
    del batch
    log(f"warmed {len(examples)} shapes ({' '.join(took)}); route "
        f"{trainer.attn_impl}/{trainer.attn_grid}; set-up so far {time.perf_counter() - t0:.1f}s")

    # 4. the first three steps, through the window's own call and feed.
    state, step_idx, feed, program, probed = first_steps(trainer, loader, key, state)

    # The window.
    backend_compiles = []
    _listen_compiles(backend_compiles)
    traced = {}
    logdir = root / TRACE_DIR / cell.name
    last = {}

    def start_trace(n):
        if trace and not traced and time.perf_counter() >= t_open + seconds * TRACE_FROM:
            t = time.perf_counter()
            if "out" in last:
                jax.block_until_ready(last["out"])  # the traced window starts idle
            shutil.rmtree(logdir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1  # the harness's spans, not the runtime's
            jax.profiler.start_trace(str(logdir), profiler_options=options)
            traced["span"] = jax.profiler.TraceAnnotation(xplane.WINDOW)
            traced["span"].__enter__()
            traced["first"] = n
            traced["start_s"] = time.perf_counter() - t  # not the data path's wait

    if trace:  # keep the last step's outputs, so the trace can start on an idle device
        inner = trainer._train_step

        def remember(state_, batch_):
            last["out"] = inner(state_, batch_)
            return last["out"]

        trainer._train_step = remember
    c0, r0 = compiles.value, realize.value
    del backend_compiles[:]
    t_open = time.perf_counter()
    setup_s = t_open - t0
    feed.start_pass(deadline=t_open + seconds, keep=trace, before_pull=start_trace)
    state, step_idx = trainer.train_epoch(state, epoch=0, start_step=step_idx)
    with jax.profiler.TraceAnnotation("bench/sync"):
        jax.block_until_ready(state)
    t_close = time.perf_counter()
    if traced:
        traced["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    window = {
        "seconds": t_close - t_open,
        "steps": len(feed.records),
        "compiles": compiles.value - c0,
        "backend_compiles": len(backend_compiles),
        "realize_s": realize.value - r0 - traced.get("start_s", 0.0),
        "records": list(feed.records),
    }
    kept = feed.kept[traced["first"]:] if traced else []
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window {window['seconds']:.3f}s {window['steps']} steps; compiles in window "
        f"{window['compiles']:g} (backend {window['backend_compiles']}); peak bytes {peak}")

    feed.close()
    del state, last
    trainer._train_step = None
    gc.collect()

    result = {
        "window": window,
        "setup_s": setup_s,
        "warm_shapes": len(examples),
        "memory_peak_bytes": peak,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }
    if traced:
        t = time.perf_counter()
        result["trace"] = _read_trace(logdir, cell, trainer.model.cfg, kept)
        log(f"trace read in {time.perf_counter() - t:.1f}s; {result['trace']['modules']} step "
            f"programs ran in the traced window, {len(kept)} steps pulled")

    # The comparison.
    t = time.perf_counter()
    result["compared"], _ = verdict(
        cell, seed, program, probed, loader.last_audit, loader.layout.needs_segments
    )
    result["correct"] = check.passed(result["compared"])
    log(f"reference and comparison in {time.perf_counter() - t:.1f}s")
    return result


def _listen_compiles(sink: list) -> None:
    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            sink.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)


def kernel_patterns(families: dict) -> dict:
    """Each kernel family's pattern of kernel names (``flash_fwd|flash_dq``)
    as a pattern over the trace's operations: a Mosaic call whose HLO
    instruction the pattern names (``%flash_fwd.3 = ...``)."""
    return {f: rf"(?s)^%?(?:{p})(?:\.\S*)? = .*{MOSAIC}" for f, p in families.items()}


def _read_trace(logdir: pathlib.Path, cell, arch, kept) -> dict:
    """Device numbers of the traced window and the work its steps required:
    the model's operations (``step_flops``), and the operations and bytes of
    each kernel family the flops module declares (``KERNELS``,
    ``kernel_work``); a module that declares none counts no kernel."""
    paths = sorted(logdir.rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {logdir}")
    flops_mod = cell.module("flops", cell.config["flops"])
    families = getattr(flops_mod, "KERNELS", {})
    red = xplane.reduce(xplane.load(paths[-1]), kernels=kernel_patterns(families), module=TRAIN_PROGRAM)
    docs = [segment_lengths(s) for s in kept]
    shapes = [step_shape(s) for s in kept]
    elem = np.dtype(arch.dtype).itemsize
    red["path"] = str(paths[-1])
    red["steps"] = len(kept)
    red["model_flops"] = sum(flops_mod.step_flops(cell.config, d) for d in docs)
    red["kernel_work"] = [
        flops_mod.kernel_work(cell.config, d, shp, elem) for d, shp in zip(docs, shapes)
    ] if families else []
    return red
