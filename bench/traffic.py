"""The one general generator: drives a compiled system as a traffic mix
file (``traffic/<mix>.json``) says.

A mix names its ``kind``, and the rest of the file holds the kind's
parameters, so a new mix of a known kind is a new data file.  A kind is
the code that drives the system, ``kinds/<kind>.py``, found by that name
(:func:`make`), so a new kind is a new file too.  Every kind makes its
inputs from the seed during set-up, never inside the window, and gives
every seed the same sizes: the seed changes the values only.

A kind's module defines ``Kind``, a subclass of :class:`Kind`, with

* ``setup()`` -- make the inputs and warm every shape the window uses;
* ``window(seconds, tracer=None) -> Window`` -- the measured window;
* ``samples() -> (shared, ins, outs)`` -- after the window, rows of what
  the timed path produced and the inputs they came from, for
  ``check.py`` to compare with the float64 reference;
* ``free()`` -- drop what the device holds, before the reference runs;

and, for ``control_readings.py``, ``make_inputs()`` and ``batches()``.
It may add numbers of its own to the comparison (:meth:`Kind.checks`).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

KINDS = Path(__file__).resolve().parent / "kinds"
#: Rows of each batch whose outputs are compared, the batch's first and
#: last among them.
SAMPLE_PER_BATCH = 32


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock."""

    wall_s: float
    calls: int
    batches: int
    elements: int
    failed: int = 0
    #: host seconds of each call, in order
    call_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def attempted(self) -> int:
        return self.batches


def _key_words(ss: np.random.SeedSequence) -> np.ndarray:
    return ss.generate_state(2, np.uint32)


@functools.partial(jax.jit, static_argnums=1)
def _uniform(words, shape):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)


def uniform_on_device(ss: np.random.SeedSequence, shape):
    """Uniform [-1, 1) float32 of ``shape``, made on the default device
    in one call from the seed."""
    return _uniform(jnp.asarray(_key_words(ss)), tuple(shape))


def ins_cat(parts: Dict[str, List[np.ndarray]]) -> Dict[str, np.ndarray]:
    return {k: np.concatenate(v) for k, v in parts.items()}


def sample_rows(rng: np.random.Generator, n_batches: int,
                batch: int) -> np.ndarray:
    """``SAMPLE_PER_BATCH`` distinct rows of each batch, its first and
    last included, ascending."""
    rows = []
    for b in range(n_batches):
        k = min(SAMPLE_PER_BATCH, batch)
        inner = rng.choice(np.arange(1, batch - 1), max(0, k - 2),
                           replace=False) if batch > 2 else []
        rows.append(np.unique(np.concatenate(
            [[0, batch - 1], inner]).astype(np.int64)) + b * batch)
    return np.concatenate(rows)


class Kind:
    """Shared set-up: names of the program's streams, shared operands."""

    def __init__(self, system, program, cfg: dict, mix: dict,
                 seed: int) -> None:
        self.system = system
        self.program = program
        self.cfg = cfg
        self.mix = mix
        self.E = system.plan.batch_elements
        chain = system.chain
        #: qualified host stream ("s0.u") -> program variable ("u")
        self.inputs = {
            f"{s.name}.{n}": n
            for i, s in enumerate(chain.stages)
            for n, _ in chain.host_element_inputs(i)
        }
        self.outputs = {
            f"{s.name}.{n}": n
            for i, s in enumerate(chain.stages)
            for n, _ in chain.chain_outputs(i)
        }
        if sorted(self.inputs.values()) != sorted(program.ELEMENT_INPUTS):
            raise ValueError(f"program inputs {sorted(self.inputs.values())}"
                             f" != {program.ELEMENT_INPUTS}")
        if sorted(self.outputs.values()) != sorted(program.OUTPUTS):
            raise ValueError(f"program outputs {sorted(self.outputs.values())}"
                             f" != {program.OUTPUTS}")
        self.elem_shape = (cfg["p"],) * 3
        ss = np.random.SeedSequence(seed)
        self._ss_shared, self._ss_inputs, self._ss_sample = ss.spawn(3)
        rng = np.random.default_rng(self._ss_shared)
        shared_names = sorted(chain.shared_operands())
        if shared_names != sorted(program.SHARED):
            raise ValueError(f"shared operands {shared_names} != "
                             f"{program.SHARED}")
        self.shared = {
            name: rng.uniform(-1, 1, chain.shared_operands()[name].shape
                              ).astype(np.float32)
            for name in shared_names
        }
        self.sample_rng = np.random.default_rng(self._ss_sample)

    def _input_seeds(self) -> Dict[str, np.random.SeedSequence]:
        names = sorted(self.program.ELEMENT_INPUTS)
        return dict(zip(names, self._ss_inputs.spawn(len(names))))

    def end_to_end(self, window: Window) -> Dict[str, float]:
        """All elements completed in the window over all its time."""
        return {"elements_per_s": window.elements / window.wall_s}

    def checks(self) -> Dict[str, float]:
        """Numbers of the kind's own for the comparison, each limited by
        the configuration's ``limits`` under the same name; read after
        :meth:`samples`."""
        return {}

    def _call(self, tracer, **kw):
        if tracer:
            with tracer.span("call", "bench.call"):
                return self.system.run(shared=self.shared, tracer=tracer,
                                       **kw)
        return self.system.run(shared=self.shared, **kw)


def load_kind(name: str):
    """The ``Kind`` class of ``kinds/<name>.py``."""
    path = KINDS / f"{name}.py"
    if not path.exists():
        known = sorted(p.stem for p in KINDS.glob("*.py"))
        raise ValueError(f"unknown traffic kind {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Kind


def make(system, program, cfg: dict, mix: dict, seed: int) -> Kind:
    return load_kind(mix["kind"])(system, program, cfg, mix, seed)
