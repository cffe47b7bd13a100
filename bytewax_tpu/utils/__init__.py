"""Small shared helpers."""

import glob
import os
import re
from typing import (
    Callable,
    Dict,
    Iterable,
    List,
    Mapping,
    Tuple,
    TypeVar,
)

X = TypeVar("X")

__all__ = [
    "chip_env",
    "cpu_asked_for",
    "force_cpu_mesh",
    "force_platform",
    "local_chip_count",
    "partition",
]


def cpu_asked_for(env: Mapping[str, str] = os.environ) -> bool:
    """Whether ``env`` sends jax to the CPU backend by name
    (``BYTEWAX_TPU_PLATFORM=cpu`` / ``JAX_PLATFORMS=cpu``) — the
    explicit waiver that tells a deliberate CPU run from an
    accelerator that silently did not come up."""
    return "cpu" in (env.get("BYTEWAX_TPU_PLATFORM"), env.get("JAX_PLATFORMS"))


def local_chip_count() -> int:
    """How many TPU chips this host exposes, counted from their
    device nodes — without touching jax, so a launcher that only
    spawns children stays off the chips itself."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def chip_env(
    proc_id: int, proc_count: int, env: Dict[str, str]
) -> Dict[str, str]:
    """What a launcher adds to the environment of child ``proc_id``
    of ``proc_count`` co-located cluster processes so that each owns
    one chip (docs/deployment.md "One process per chip").

    A chip belongs to one process at a time: children started with
    the same environment would all open every chip, and all but the
    first would fail or hang.  libtpu's chip-visibility variables
    give each child its own chip instead.  Nothing is added for a
    single process (it shards over every chip itself), on a host
    without chips, for children sent to the CPU backend by name, or
    where the caller already chose the chips.  Asking for more
    processes than the host has chips raises before anything starts:
    no child may end up sharing a chip or on the CPU unasked."""
    chips = local_chip_count()
    if (
        proc_count <= 1
        or not chips
        or cpu_asked_for(env)
        or "TPU_VISIBLE_CHIPS" in env
    ):
        return {}
    if proc_count > chips:
        msg = (
            f"{proc_count} device-tier processes were asked for on a "
            f"host with {chips} chip(s); a chip belongs to one process "
            "at a time. Run at most one process per chip (more lanes "
            "with -w share a process), or send the children to the "
            "CPU backend with BYTEWAX_TPU_PLATFORM=cpu"
        )
        raise RuntimeError(msg)
    return {
        "TPU_VISIBLE_CHIPS": str(proc_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def force_platform(platform: str, n_devices=None) -> None:
    """Steer jax onto ``platform`` before it initializes a backend.

    Sets both the ``JAX_PLATFORMS`` environment variable (inherited
    by child processes) and the ``jax_platforms`` config flag (read
    by this process even when ``jax`` was imported earlier; the
    backend is created lazily on the first device query). With
    ``n_devices``, also requests that many virtual host-platform
    devices via ``XLA_FLAGS``, upgrading an inherited smaller count.

    Best-effort: does NOT query devices, so it never triggers backend
    init itself and silently has no effect if a backend already came
    up. Use :func:`force_cpu_mesh` when the caller needs the result
    verified.
    """
    os.environ["JAX_PLATFORMS"] = platform
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = "--xla_force_host_platform_device_count="
        m = re.search(re.escape(opt) + r"(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (flags + f" {opt}{n_devices}").strip()
        elif int(m.group(1)) < n_devices:
            os.environ["XLA_FLAGS"] = (
                flags[: m.start()] + f"{opt}{n_devices}" + flags[m.end() :]
            )

    import jax

    jax.config.update("jax_platforms", platform)


def force_cpu_mesh(n_devices: int) -> None:
    """Force jax onto the CPU backend with ``n_devices`` virtual
    devices, verifying the result.

    Must run before jax initializes a backend; raises if a backend
    already came up on a non-CPU platform or with too few devices
    (this check itself triggers backend init, which is the point —
    fail loudly here rather than hang later).
    """
    force_platform("cpu", n_devices)

    import jax

    platform = jax.devices()[0].platform
    if platform != "cpu":
        msg = (
            f"jax backend already initialized on {platform!r}; "
            "force_cpu_mesh must run before any jax device query"
        )
        raise RuntimeError(msg)
    avail = jax.device_count()
    if avail < n_devices:
        msg = (
            f"virtual CPU mesh has {avail} devices, need {n_devices}; "
            "jax initialized before force_cpu_mesh could set XLA_FLAGS "
            f"(flags now: {os.environ['XLA_FLAGS']!r})"
        )
        raise RuntimeError(msg)


def partition(
    xs: Iterable[X], pred: Callable[[X], bool]
) -> Tuple[List[X], List[X]]:
    """Split an iterable into (matching, not-matching) lists, keeping
    order."""
    trues: List[X] = []
    falses: List[X] = []
    for x in xs:
        if pred(x):
            trues.append(x)
        else:
            falses.append(x)
    return trues, falses
