"""What the training cells share: the benchmark's batches and weights, its
clock on the trainer's step loop, the tap on the step function, the
reference optimizer and the comparison with the plain reference.

Nothing here imports the program at module level; the trainer is built
by :func:`build_trainer` from the configuration file.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone under Adam: it is left out of the
# parameter-change comparison
STILL_LEAF = 1e-3


class WindowClosed(Exception):
    """Raised from the batch source at the first request after the
    window: it ends the trainer's ``run`` there."""


class AdamState(NamedTuple):
    """The optimizer state's layout: the step count, then the first and
    second moments, each shaped like the parameters."""
    step: Any
    m: Any
    v: Any


# ---------------------------------------------------------------------------
# traffic: batches and weights from the seed
# ---------------------------------------------------------------------------

class BatchSource:
    """``batch_at(t)``: token ids drawn uniformly from the vocabulary,
    from (seed, t) alone, so every step's rows differ and a seed gives
    the same batches in every run. ``on_request(t)`` runs first."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 on_request: Optional[Callable[[int], None]] = None):
        self.seed, self.batch, self.seq, self.vocab = seed, batch, seq, vocab
        self.on_request = on_request

    def tokens(self, t: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, t]))
        ids = rng.integers(0, self.vocab, size=(self.batch, self.seq + 1),
                           dtype=np.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

    def batch_at(self, t: int) -> Dict[str, np.ndarray]:
        if self.on_request is not None:
            self.on_request(t)
        return self.tokens(t)


def weights_key(seed: int):
    import jax.numpy as jnp
    from bench.harness import seed_words
    return jnp.asarray(seed_words(seed), dtype=jnp.uint32)


def make_weights(reference, config: dict, seed: int):
    """The reference's initializer, in one jitted call on the device."""
    import jax
    return jax.jit(functools.partial(reference.init_params, config))(
        weights_key(seed))


def make_opt_state(params, seed: int, step: int) -> AdamState:
    """A plausible optimizer state at ``step`` (moments drawn from the
    seed), for the crash image the resume cell restores."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(weights_key(seed), 1)

    @jax.jit
    def draw(params, key):
        leaves, tree = jax.tree.flatten(params)
        ks = jax.random.split(key, 2 * len(leaves))
        m = [1e-5 * jax.random.normal(k, p.shape, jnp.float32)
             for k, p in zip(ks[::2], leaves)]
        v = [jnp.square(1e-5 * jax.random.normal(k, p.shape, jnp.float32))
             + 1e-12 for k, p in zip(ks[1::2], leaves)]
        return tree.unflatten(m), tree.unflatten(v)

    m, v = draw(params, key)
    return AdamState(step=jnp.asarray(step, jnp.int32), m=m, v=v)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def build_trainer(config: dict, workdir: str, seed: int, *, mode: str,
                  slot_every: int, n_slots: int):
    from repro.configs.base import ModelConfig, TrainConfig
    from repro.launch.train import ADCCTrainer

    model = ModelConfig(**config["model"])
    train = TrainConfig(**config["train"], seed=seed % 2 ** 31)
    return ADCCTrainer(model, train, workdir, batch=config["shape"]["batch"],
                       seq=config["shape"]["seq"], slot_every=slot_every,
                       n_slots=n_slots, mode=mode)


def check_layout(trainer, params) -> None:
    """The benchmark's weights must have the trainer's tree and shapes."""
    import jax
    want, _ = trainer.api.abstract_init(jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    exp = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != exp:
        raise ValueError(f"weights layout {got} != trainer's {exp}")


def give_weights(trainer, params) -> None:
    """Start the trainer from the benchmark's weights: its ``run`` takes
    them from the model's ``init`` when it finds nothing to recover. The
    weights are handed over once, so the trainer holds no second copy."""
    held = [params]
    trainer.api = dataclasses.replace(trainer.api,
                                      init=lambda key: (held.pop(), None))


class Clock:
    """The benchmark's clock on the step loop. ``request(t)`` is called
    when the trainer asks for batch t; the window opens at ``open_step``
    and closes at the first request at least ``seconds`` later whose
    step is ``align`` steps on from the opening."""

    def __init__(self, open_step: int, seconds: float, align: int = 1,
                 on_open: Callable[[], None] = lambda: None,
                 on_close: Callable[[], None] = lambda: None,
                 spans: bool = False):
        self.open_step, self.seconds, self.align = open_step, seconds, align
        self.on_open, self.on_close = on_open, on_close
        self.times: Dict[int, float] = {}
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.close_step: Optional[int] = None
        self._spans = spans
        self._after = None

    def request(self, t: int) -> None:
        now = time.perf_counter()
        self.end_span()
        self.times[t] = now
        if t == self.open_step:
            self.t_open = now
            self.on_open()
        elif (self.t_open is not None and now - self.t_open >= self.seconds
              and (t - self.open_step) % self.align == 0):
            self.t_close, self.close_step = now, t
            self.on_close()
            raise WindowClosed

    def begin_span(self, name: str) -> None:
        if self._spans:
            import jax
            self.end_span()
            self._after = jax.profiler.TraceAnnotation(name)
            self._after.__enter__()

    def end_span(self) -> None:
        if self._after is not None:
            self._after.__exit__(None, None, None)
            self._after = None

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def steps(self) -> int:
        return self.close_step - self.open_step

    def step_walls(self) -> Dict[int, float]:
        """Wall of each window step: from its batch request to the next."""
        return {t: self.times[t + 1] - self.times[t]
                for t in range(self.open_step, self.close_step)}


class StepTap:
    """Wraps the trainer's step function: keeps every step's loss and
    hands the first ``capture`` steps' inputs and outputs to ``on_step``
    (which reduces them at once, so nothing large is held)."""

    def __init__(self, fn, capture: int,
                 on_step: Callable[[int, tuple, tuple], None],
                 clock: Optional[Clock] = None,
                 each: Optional[Callable[[int, tuple], None]] = None):
        self.fn, self.capture, self.on_step = fn, capture, on_step
        self.clock, self.each = clock, each
        self.calls = 0
        self.losses: List[Any] = []

    def __call__(self, params, opt_state, err_state, batch, rng):
        if self.clock is not None:
            self.clock.begin_span("bench.step_call")
        out = self.fn(params, opt_state, err_state, batch, rng)
        if self.clock is not None:
            self.clock.begin_span("bench.after_step")
        self.losses.append(out[3]["loss"])
        if self.calls < self.capture:
            self.on_step(self.calls, (params, opt_state), out)
        if self.each is not None:
            self.each(self.calls, out)
        self.calls += 1
        return out


# ---------------------------------------------------------------------------
# slots: what the writer was handed, against what it wrote
# ---------------------------------------------------------------------------

def state_keys(tree) -> List[str]:
    """Each leaf's name in a slot: its tree path, joined by '/' (the file
    is the name with '/' as '__', plus '.npy')."""
    import jax

    def part(p):
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                return str(getattr(p, attr))
        return str(p)
    return ["/".join(part(p) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


_MIX_INDEX, _MIX_OUT = 0x9E3779B1, 0x85EBCA6B


def _device_digest(x):
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    idx = jnp.arange(bits.size, dtype=jnp.uint32)
    mixed = (bits ^ (idx * jnp.uint32(_MIX_INDEX))) * jnp.uint32(_MIX_OUT)
    return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                      jnp.sum(mixed, dtype=jnp.uint32)])


@functools.lru_cache(maxsize=None)
def _digest_jit():
    import jax
    return jax.jit(lambda leaves: [_device_digest(x) for x in leaves])


def state_digest(tree) -> Dict[str, tuple]:
    """Per leaf, two 32-bit words of its bits (their sum, and the sum of
    the bits mixed with their position), taken on the device: two arrays
    differ in a bit of a leaf and the words almost surely differ."""
    import jax
    words = jax.device_get(_digest_jit()(jax.tree.leaves(tree)))
    return {k: tuple(int(w) for w in d)
            for k, d in zip(state_keys(tree), words)}


def array_digest(a: np.ndarray) -> tuple:
    """:func:`state_digest`'s words of one array, on the host."""
    bits = np.ascontiguousarray(a).view(np.uint32).reshape(-1)
    idx = np.arange(bits.size, dtype=np.uint32)
    mixed = (bits ^ (idx * np.uint32(_MIX_INDEX))) * np.uint32(_MIX_OUT)
    return (int(bits.sum(dtype=np.uint32)), int(mixed.sum(dtype=np.uint32)))


# ---------------------------------------------------------------------------
# reference optimizer and comparison
# ---------------------------------------------------------------------------

def learning_rate(train: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to a tenth."""
    warm = min(step / max(train["warmup_steps"], 1), 1.0)
    prog = min(max((step - train["warmup_steps"])
                   / max(train["total_steps"] - train["warmup_steps"], 1),
                   0.0), 1.0)
    return train["learning_rate"] * warm * (
        0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * prog)))


def _adamw_leaf(g, m, v, p, lr, c1, c2, b1, b2, eps, wd):
    import jax.numpy as jnp
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), m, v


@functools.lru_cache(maxsize=None)
def _adamw_jit():
    import jax
    return jax.jit(_adamw_leaf)


def adamw(train: dict, grads, state: AdamState, params):
    """One decoupled-weight-decay Adam step (Loshchilov & Hutter), float32."""
    import jax
    import jax.numpy as jnp

    step = int(state.step) + 1
    b1, b2 = train["beta1"], train["beta2"]
    scalars = (learning_rate(train, step), 1.0 - b1 ** step,
               1.0 - b2 ** step, b1, b2, train["eps"], train["weight_decay"])
    out = jax.tree.map(lambda g, m, v, p: _adamw_jit()(g, m, v, p, *scalars),
                       grads, state.m, state.v, params)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), AdamState(step=jnp.asarray(step, jnp.int32),
                              m=pick(1), v=pick(2))


def leaf_reduce(fn, tree) -> np.ndarray:
    """``fn`` of each leaf in float32, as float64 on the host."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.device_get([fn(x.astype(jnp.float32))
                                      for x in jax.tree.leaves(tree)]),
                      np.float64)


def leaf_norms(tree) -> np.ndarray:
    import jax.numpy as jnp
    return leaf_reduce(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def diff_norms(a, b) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                                   - y.astype(jnp.float32), a, b))


def norm_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Optional[np.ndarray] = None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of the reference's norm of that leaf and of the
    median leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    med = float(np.median(ref[idx]))
    denom = np.maximum(np.maximum(ref[idx], med), 1e-30)
    return float(np.max(np.abs(prog[idx] - ref[idx]) / denom))


def moved_leaves(ref_grad_norms: Sequence[float]) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= STILL_LEAF * np.median(g)


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


@dataclasses.dataclass
class Readings:
    """What one side (the program, or a reference put in its place) gives
    over the compared steps."""
    losses: List[float]
    grad_norms: np.ndarray        # first step's gradient, per leaf
    delta_norms: np.ndarray       # change of the parameters, per leaf
    # per compared step and leaf: the sum of the parameters after the
    # step (what the ledger records), and, on the reference's side, the
    # sum of their magnitudes that a gap is measured against
    param_sums: Optional[np.ndarray] = None
    param_l1: Optional[np.ndarray] = None


def reference_steps(reference, config: dict, params, state: AdamState,
                    batches: Sequence[Dict[str, np.ndarray]], *,
                    operand_dtype=None) -> Readings:
    """The plain reference trained over ``batches`` from (params, state)."""
    import jax
    import jax.numpy as jnp

    start = params
    losses, gnorms, sums, l1 = [], None, [], []
    for b in batches:
        loss, grads = reference.loss_and_grad(
            config, params, jnp.asarray(b["tokens"]),
            jnp.asarray(b["labels"]), operand_dtype=operand_dtype)
        losses.append(float(loss))
        if gnorms is None:
            gnorms = leaf_norms(grads)
        params, state = adamw(config["train"], grads, state, params)
        del grads
        sums.append(leaf_reduce(jnp.sum, params))
        l1.append(leaf_reduce(lambda x: jnp.sum(jnp.abs(x)), params))
    return Readings(losses, gnorms, diff_norms(params, start),
                    np.stack(sums), np.stack(l1))


def sum_gap(prog: np.ndarray, ref: np.ndarray, ref_l1: np.ndarray) -> float:
    """Worst step and leaf's gap between the program's parameter sum and
    the reference's, over the reference's sum of magnitudes of that leaf
    (a zero-mean leaf's sum is near nought)."""
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.maximum(ref_l1, 1e-30)))


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    got = {
        "loss_gap": loss_gap(prog.losses, ref.losses),
        "grad_gap": norm_gap(prog.grad_norms, ref.grad_norms),
        "delta_gap": norm_gap(prog.delta_norms, ref.delta_norms,
                              moved_leaves(ref.grad_norms)),
    }
    if prog.param_sums is not None:
        got["ledger_gap"] = sum_gap(prog.param_sums, ref.param_sums,
                                    ref.param_l1)
    return got


def zero_state(params) -> AdamState:
    import jax
    import jax.numpy as jnp
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamState(step=jnp.asarray(0, jnp.int32),
                     m=jax.tree.map(z, params), v=jax.tree.map(z, params))


def limits(cell, kind: str) -> Dict[str, float]:
    """The limits of ``correct`` for a driver kind, from the
    configuration's ``limits.json``."""
    import json
    import os
    with open(os.path.join(cell.config_dir, "limits.json")) as fh:
        return json.load(fh)[kind]


def checks(cell, kind: str, got: Dict[str, float]) -> Dict[str, Any]:
    """Each number that ``limits.json`` holds for ``kind``, beside its
    limit."""
    from bench.harness import Check
    return {k: Check(got[k], lim) for k, lim in limits(cell, kind).items()}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
