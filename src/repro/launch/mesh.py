"""Production mesh construction (pure functions — importing this module
never touches jax device state). ``make_serve_mesh`` is the serve-cell
entry point: it takes the ``--mesh data=4`` CLI spelling and builds a
mesh over a *prefix* of the local devices (unlike ``jax.make_mesh`` it
does not require the axis product to cover every device — a 2-way cell
on a 4-device host is legal)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "parse_mesh_spec", "make_serve_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules here rely
    on GSPMD propagation, and JAX 0.9 defaults to ``Explicit`` axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("pod",) "data", "model" — pod is DCN-level data parallelism,
    data is intra-pod DP/FSDP, model is TP/EP/SP (DESIGN.md §4).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(arg: str) -> dict[str, int]:
    """``"data=4"`` / ``"pod=2,data=2"`` -> an ordered ``{axis: size}``."""
    axes: dict[str, int] = {}
    for part in arg.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        try:
            n = int(size)
        except ValueError:
            n = 0
        if not name or n < 1:
            raise ValueError(
                f"mesh spec entries are axis=size (e.g. 'data=4'), "
                f"got {part!r} in {arg!r}")
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r} in {arg!r}")
        axes[name] = n
    return axes


def make_serve_mesh(spec: str | dict[str, int]):
    """Mesh for a serve cell from a ``--mesh`` spec string or axis dict.

    Uses the first ``prod(sizes)`` local devices (axis order = spec
    order), so a cell smaller than the host is legal. On a CPU host,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` fakes N
    devices — the tests/CI topology."""
    import numpy as np

    axes = parse_mesh_spec(spec) if isinstance(spec, str) else dict(spec)
    n = 1
    for s in axes.values():
        n *= s
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {axes} needs {n} devices but only {len(devices)} "
            f"are visible (on CPU, XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} forces {n})")
    arr = np.asarray(devices[:n]).reshape(tuple(axes.values()))
    return jax.sharding.Mesh(arr, tuple(axes))
