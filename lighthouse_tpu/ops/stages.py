"""Stage programs: the BLS kernels a verify dispatches, jitted, and their
start-up store.

A kernel declared with :func:`stage` dispatches as ``jax.jit`` does until
an executable for the same argument types is registered on it: a call
whose arguments all live on that executable's device then runs it
directly, with no trace.  :func:`compile_stored` fills that registry at
start-up from the store, a directory beside JAX's persistent compilation
cache (:func:`store_dir`) that keeps each program as an exported module
(``jax.export``).  Tracing and lowering a stage program costs seconds of
Python (the kernels are unrolled); reading its exported module back and
lowering the call to it costs a tenth of that, and the compile is then a
hit in JAX's persistent cache.  A module is found by the program's name,
its argument types, the JAX version, the platform, the multiply
lowering (``bigint.mxu_mode``) and a digest of the sources that trace it
(:func:`digest`), so an edit to a kernel never reads a stale module;
while a test has swapped a kernel for a stand-in, nothing is read or
written (:func:`pristine`).
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
from pathlib import Path

import jax
import numpy as np
from jax import export

from . import bigint as bi

#: the sources whose code a stage program or a verify's list of them is
#: traced from
_SOURCES = ("ops", "crypto")
_PACKAGE = Path(__file__).resolve().parents[1]


class Stage:
    """A jitted kernel with a registry of loaded executables by argument
    types (:meth:`register`); every other attribute is the ``jax.jit``
    wrapper's (``lower``, ``trace``, ...)."""

    def __init__(self, fun):
        self._jit = _jit(fun)
        functools.update_wrapper(self, fun)
        self._loaded: dict = {}

    def __call__(self, *args, **kwargs):
        if self._loaded and not kwargs:
            hit = self._loaded.get(signature(args))
            if hit is not None and _on_device(args, hit[0]):
                return hit[1](*args)
        return self._jit(*args, **kwargs)

    def __getattr__(self, name):
        if name == "_jit":              # not set yet: no recursion
            raise AttributeError(name)
        return getattr(self._jit, name)

    def register(self, args, compiled) -> None:
        """Run ``compiled`` for calls with the types of ``args``
        (``jax.ShapeDtypeStruct``s) on its device."""
        device, = compiled.input_shardings[0][0].device_set
        self._loaded[signature(args)] = (device, compiled)


@functools.cache
def _jit(fun):
    """One ``jax.jit`` wrapper per function, kept: its trace cache lives
    as long as the wrapper."""
    return jax.jit(fun)


#: declares a stage program: ``@stage`` in place of ``@jax.jit``
stage = Stage


def signature(args) -> tuple:
    """The types of ``args`` (arrays, or ``jax.ShapeDtypeStruct``s): shape,
    dtype and weak type of each."""
    return tuple((tuple(a.shape), np.dtype(a.dtype).name,
                  bool(getattr(a, "weak_type", False)))
                 for a in (a if isinstance(a, jax.ShapeDtypeStruct)
                           else jax.typeof(a) for a in args))


def _on_device(args, device) -> bool:
    """No argument is a tracer or an array placed elsewhere than on
    ``device`` alone."""
    for a in args:
        if isinstance(a, jax.core.Tracer):
            return False
        if isinstance(a, jax.Array) and a.devices() != {device}:
            return False
    return True


#: (module namespace, {name: function}) as each module was loaded
_FROZEN: list = []


def freeze(namespace: dict) -> None:
    """Note the functions of a module the stage programs are traced from,
    as loaded (:func:`pristine`)."""
    _FROZEN.append((namespace, {n: f for n, f in namespace.items()
                                if callable(f)}))


def pristine() -> bool:
    """Every function :func:`freeze` noted is still in its module: a
    program traced now is the one its sources describe (a test that
    swaps a kernel for a stand-in must neither read nor write the
    store)."""
    return all(namespace.get(n) is f for namespace, functions in _FROZEN
               for n, f in functions.items())


def store_dir() -> Path | None:
    """The store: ``lighthouse_tpu_stages`` in JAX's persistent
    compilation cache directory, None where no cache is set."""
    cache = jax.config.jax_compilation_cache_dir
    return Path(cache) / "lighthouse_tpu_stages" if cache else None


@functools.cache
def digest() -> str:
    """What a stored module or list depends on besides its own key: the
    JAX version, the platform, the 64-bit flag and the sources that
    trace the stage programs."""
    h = hashlib.sha256(repr((jax.__version__, jax.default_backend(),
                             jax.config.jax_enable_x64)).encode())
    for top in _SOURCES:
        for path in sorted((_PACKAGE / top).rglob("*.py")):
            h.update(str(path.relative_to(_PACKAGE)).encode())
            h.update(path.read_bytes())
    return h.hexdigest()


def _key(*parts) -> str:
    # the multiply lowering is chosen at run time, and traced through
    return hashlib.sha256(repr((digest(), bi.mxu_mode(), *parts)).encode()
                          ).hexdigest()[:32]


def _write(path: Path, data: bytes) -> None:
    """Write whole or not at all: concurrent processes may store the
    same entry."""
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    tmp.write_bytes(data)
    os.replace(tmp, path)


def compile_stored(program: Stage, args, store: Path):
    """Compile ``program`` for ``args`` (``jax.ShapeDtypeStruct``s) from
    its exported module in ``store``, exporting and storing it first
    where it is missing, and register the executable on ``program``.
    The call is lowered from the stored bytes in every process, so each
    lowers the same module and the compile hits JAX's persistent cache
    after the first.  Returns the ``jax.stages.Compiled``."""
    name = program.__name__
    path = store / f"{name}-{_key(name, signature(args))}.exported"
    try:
        blob = path.read_bytes()
    except FileNotFoundError:
        blob = export.export(program._jit)(*args).serialize()
        _write(path, blob)
    exported = export.deserialize(bytearray(blob))

    def call(*a):
        return exported.call(*a)

    call.__name__ = name            # the program's name in device traces
    compiled = _jit(call).lower(*args).compile()
    program.register(args, compiled)
    return compiled


def read_list(store: Path, key_parts) -> list | None:
    """A stored list of ``(name, args)`` (see :func:`write_list`), or
    None."""
    path = store / f"list-{_key(*key_parts)}.json"
    try:
        entries = json.loads(path.read_text())
    except FileNotFoundError:
        return None
    return [(name, tuple(jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                              weak_type=weak)
                         for shape, dtype, weak in args))
            for name, args in entries]


def write_list(store: Path, key_parts, entries) -> None:
    """Store ``entries``, ``(name, args)`` pairs such as the stage
    programs a verify of one shape dispatches, under ``key_parts``."""
    _write(store / f"list-{_key(*key_parts)}.json", json.dumps(
        [[name, [list(s) for s in signature(args)]]
         for name, args in entries]).encode())
