"""Kernel benchmark registry: uniform access to the paper's five benchmarks
(+ flash attention) for tests, benchmarks and examples.

Each entry binds: tuning space, config→kernel-kwargs dispatch, the jnp oracle,
the portable workload model g(TP, I), and a catalog of inputs (the paper's
input-portability experiments need several per benchmark).

Registration is decorator-based and lives with each kernel package: a
package's ``__init__`` declares

    @register_benchmark("matmul")
    def _benchmark() -> KernelBenchmark: ...

and ``BENCHMARKS`` discovers the packages lazily on first access (so plain
``import repro.kernels.matmul`` stays cheap and adding a kernel package
never touches this module).
"""
from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from typing import Any, Callable, Dict, Iterator, Mapping, Tuple

from repro.core.tuning_space import Config, TuningSpace


@dataclasses.dataclass
class KernelBenchmark:
    name: str
    make_space: Callable[[], TuningSpace]
    workload_fn: Callable[[Config, Any], Dict[str, float]]
    default_input: Any
    inputs: Dict[str, Any]
    make_args: Callable[[Any, Any], Tuple]
    run: Callable[..., Any]       # run(cfg, *args, interpret=...)
    ref: Callable[..., Any]       # ref(*args)
    # a configuration the TPU v5e compiler accepts at ``default_input``
    default_config: Config
    _space: TuningSpace = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def space(self) -> TuningSpace:
        """Memoized ``make_space()``.

        A registry space is deterministic and treated as read-only by
        every consumer (its feature matrix is literally frozen), but
        materializing one enumerates the whole constrained cross
        product — ~1ms for the larger kernels.  Hot paths that build a
        job per request (the service daemon's submit path) would
        otherwise pay that on every submit; callers that need a private
        mutable space can still call ``make_space()`` directly.
        """
        if self._space is None:
            self._space = self.make_space()
        return self._space


_FACTORIES: Dict[str, Callable[[], KernelBenchmark]] = {}


def register_benchmark(name: str):
    """Decorator for a zero-arg factory returning a ``KernelBenchmark``.

    Applied inside each kernel package's ``__init__``; the factory is built
    lazily on first registry access and cached.
    """

    def deco(factory: Callable[[], KernelBenchmark]):
        if name in _FACTORIES:
            raise ValueError(f"benchmark {name!r} registered twice")
        _FACTORIES[name] = factory
        return factory

    return deco


class _BenchmarkRegistry(Mapping):
    """Lazy name → KernelBenchmark mapping over the registered factories."""

    def __init__(self) -> None:
        self._built: Dict[str, KernelBenchmark] = {}
        self._discovered = False

    def _discover(self) -> None:
        """Import every repro.kernels subpackage so decorators run."""
        if self._discovered:
            return
        import repro.kernels as pkg

        for mod in pkgutil.iter_modules(pkg.__path__):
            if mod.ispkg:
                importlib.import_module(f"repro.kernels.{mod.name}")
        # only after every package imported cleanly — a failed import must
        # surface again on the next access, not a half-populated registry
        self._discovered = True

    def __getitem__(self, name: str) -> KernelBenchmark:
        self._discover()
        if name not in self._built:
            if name not in _FACTORIES:
                raise KeyError(
                    f"unknown benchmark {name!r}; "
                    f"registered: {sorted(_FACTORIES)}")
            bench = _FACTORIES[name]()
            if bench.name != name:
                raise ValueError(
                    f"benchmark factory for {name!r} returned name "
                    f"{bench.name!r}")
            self._built[name] = bench
        return self._built[name]

    def __iter__(self) -> Iterator[str]:
        self._discover()
        return iter(sorted(_FACTORIES))

    def __len__(self) -> int:
        self._discover()
        return len(_FACTORIES)


BENCHMARKS: Mapping[str, KernelBenchmark] = _BenchmarkRegistry()


def GEMM_FULL_SPACE() -> TuningSpace:
    """GEMM-full: the CLTune-like larger space sharing matmul's workload
    model — used for the small-space-model → big-space-search experiment
    (Fig. 8)."""
    from repro.kernels.matmul import space as matmul_space

    return matmul_space.make_full_space()
