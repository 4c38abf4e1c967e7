"""TPU task backend: runs map tasks through app-declared device kernels.

Reference scope: the worker's task execution bodies (``mr/worker.go:55-97``
map, ``:99-161`` reduce).  Everything around the execution — pull protocol,
intermediate file naming/format, atomic commit, missing-file tolerance,
completion RPCs — is untouched; this backend only swaps the *compute* inside
a task, which is exactly the boundary SURVEY.md §7 step 4 prescribes.

App contract (optional, duck-typed — the plugin boundary stays two-symbol
for portable apps):

* ``tpu_map(filename: str, raw: bytes) -> list[KeyValue] | None`` — device
  implementation of the map task.  Returning None means "this input needs
  the host path" (e.g. non-ASCII text); the runner then falls back to the
  app's ordinary ``Map`` — correctness never depends on the kernel.
* ``tpu_reduce(key, values) -> str`` — optional; defaults to the app's
  ``Reduce``.  For combiner-style apps the reduce phase is tiny (one record
  per unique key per split), so it stays on the host.

Every map task is counted as a device map or a host map (``device_maps`` /
``host_maps`` here, ``tpu_map_device`` / ``tpu_map_host`` in the tracer's
counters), so a job whose inputs took the host fallback says so.
"""

from __future__ import annotations

from dsi_tpu.mr import worker as w
from dsi_tpu.mr.plugin import load_plugin_module
from dsi_tpu.obs import count as _count


class TpuTaskRunner:
    """Backend object for ``worker_loop(task_runner=...)``."""

    def __init__(self, app_module):
        self.app = app_module
        self.tpu_map = getattr(app_module, "tpu_map", None)
        self.tpu_reduce = getattr(app_module, "tpu_reduce", None)
        self.device_maps = 0
        self.host_maps = 0
        #: What JAX reported when the backend came up (``for_app``):
        #: platform, kind, count.
        self.device: dict = {}
        if self.tpu_map is None and self.tpu_reduce is None:
            import sys

            print(
                f"mrworker: app {getattr(app_module, '__name__', app_module)} "
                "declares no tpu_map/tpu_reduce; --backend=tpu will run every "
                "task on the host path (use the tpu_wc app for the device "
                "word-count kernel)", file=sys.stderr)

    @classmethod
    def for_app(cls, name_or_path: str) -> "TpuTaskRunner":
        from dsi_tpu.utils.platformpin import require_device

        devices = require_device("mrworker --backend tpu")
        runner = cls(load_plugin_module(name_or_path))
        runner.device = {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)}
        return runner

    def run_map(self, mapf, filename: str, map_task: int, n_reduce: int,
                workdir: str = ".") -> None:
        raw = w.read_split(filename)
        kva = self.tpu_map(filename, raw) if self.tpu_map else None
        if kva is None:  # host fallback (worker.go:55-92 semantics)
            self.host_maps += 1
            _count("tpu_map_host")
            kva = mapf(filename, raw.decode("utf-8", errors="replace"))
        else:
            self.device_maps += 1
            _count("tpu_map_device")
        w.write_intermediates(kva, map_task, n_reduce, workdir)

    def run_reduce(self, reducef, reduce_task: int, n_map: int,
                   workdir: str = ".") -> None:
        w.run_reduce_task(self.tpu_reduce or reducef, reduce_task, n_map,
                          workdir)

    def report(self) -> str:
        """The line the worker prints at exit."""
        return (f"backend=tpu platform={self.device.get('platform', '')} "
                f"device_maps={self.device_maps} "
                f"host_maps={self.host_maps}")
