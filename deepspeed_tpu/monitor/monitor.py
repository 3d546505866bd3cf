"""Experiment monitoring.

TPU-native equivalent of the reference's ``deepspeed/monitor/``: ``Monitor`` ABC +
``MonitorMaster`` fan-out (``monitor/monitor.py:13,:29``) over TensorBoard
(``tensorboard.py:13``), W&B (``wandb.py:12``) and CSV (``csv_monitor.py:12``)
backends; writes happen on process rank 0 only.
"""

import csv
import json
import os
import time

from .. import comm as dist
from ..utils.logging import logger


class Monitor:
    def __init__(self, config):
        self.config = config
        self.enabled = config.enabled

    def write_events(self, event_list):
        """event_list: [(name, value, step), ...]"""
        raise NotImplementedError


class TensorBoardMonitor(Monitor):
    """Reference ``monitor/tensorboard.py:13``. Uses torch's SummaryWriter if
    importable (torch-cpu is in the image); silently disables otherwise."""

    def __init__(self, config):
        super().__init__(config.tensorboard)
        self.summary_writer = None
        if self.enabled and dist.get_rank() == 0:
            try:
                from torch.utils.tensorboard import SummaryWriter

                base = config.tensorboard.output_path or "./runs"
                self.summary_writer = SummaryWriter(
                    log_dir=os.path.join(base, config.tensorboard.job_name)
                )
            except Exception as e:  # pragma: no cover
                logger.warning(f"TensorBoard unavailable: {e}")
                self.enabled = False

    def write_events(self, event_list):
        if self.summary_writer is None:
            return
        for name, value, step in event_list:
            self.summary_writer.add_scalar(name, value, step)
        self.summary_writer.flush()


class WandbMonitor(Monitor):
    """Reference ``monitor/wandb.py:12``."""

    def __init__(self, config):
        super().__init__(config.wandb)
        self._wandb = None
        if self.enabled and dist.get_rank() == 0:
            try:
                import wandb

                wandb.init(project=config.wandb.project, group=config.wandb.group or None,
                           entity=config.wandb.team or None)
                self._wandb = wandb
            except Exception as e:  # pragma: no cover
                logger.warning(f"wandb unavailable: {e}")
                self.enabled = False

    def write_events(self, event_list):
        if self._wandb is None:
            return
        for name, value, step in event_list:
            self._wandb.log({name: value}, step=step)


class CSVMonitor(Monitor):
    """Reference ``monitor/csv_monitor.py:12``: one CSV file per metric name."""

    def __init__(self, config):
        super().__init__(config.csv_monitor)
        self.output_path = None
        if self.enabled and dist.get_rank() == 0:
            base = config.csv_monitor.output_path or "./csv_logs"
            self.output_path = os.path.join(base, config.csv_monitor.job_name)
            os.makedirs(self.output_path, exist_ok=True)

    def write_events(self, event_list):
        if self.output_path is None:
            return
        for name, value, step in event_list:
            fname = os.path.join(self.output_path, name.replace("/", "_") + ".csv")
            new = not os.path.exists(fname)
            with open(fname, "a", newline="") as f:
                w = csv.writer(f)
                if new:
                    w.writerow(["step", name])
                w.writerow([step, value])


class TraceFileMonitor(Monitor):
    """Trace-file backend: appends scalar events as JSONL next to the span
    trace (``<telemetry.output_path>/<job_name>/scalars.jsonl``), so the
    same directory holds spans AND the scalars recorded against them —
    ``tools/trace_summary.py`` joins both (e.g. flags steps whose
    ``Comm/exposed_frac`` exceeds budget). Gated on the ``telemetry``
    config block; rank 0 only."""

    def __init__(self, config):
        tel = getattr(config, "telemetry", None)
        # duck-typed stand-in for a config section: enabled + job fields
        self.config = tel
        self.enabled = bool(tel is not None and tel.enabled)
        self.path = None
        if self.enabled and dist.get_rank() == 0:
            base = tel.output_path or "./traces"
            d = os.path.join(base, tel.job_name)
            os.makedirs(d, exist_ok=True)
            self.path = os.path.join(d, "scalars.jsonl")
            # fresh run, fresh scalar stream (spans.jsonl does the same)
            open(self.path, "w").close()

    def write_events(self, event_list):
        if self.path is None:
            return
        now = time.time()
        with open(self.path, "a") as f:
            for name, value, step in event_list:
                f.write(json.dumps({"name": name, "value": float(value),
                                    "step": int(step), "time": now}) + "\n")


class MonitorMaster(Monitor):
    """Reference ``monitor/monitor.py:29``: fan out to all enabled backends.

    One failing backend (a TensorBoard/W&B import-or-IO error mid-run, a
    full disk under the CSV dir) must cost its own events, not the training
    step: each backend's write is isolated, and the first failure logs one
    warning naming the backend — later failures of the same backend are
    silent (a stuck writer at ``steps_per_print`` cadence would otherwise
    flood the log)."""

    def __init__(self, config):
        self.backends = [
            TensorBoardMonitor(config),
            WandbMonitor(config),
            CSVMonitor(config),
            TraceFileMonitor(config),
        ]
        self.enabled = any(b.enabled for b in self.backends)
        self._failed = set()

    def write_events(self, event_list):
        if not event_list or dist.get_rank() != 0:
            return
        for b in self.backends:
            if not b.enabled:
                continue
            try:
                b.write_events(event_list)
            except Exception as e:
                name = type(b).__name__
                if name not in self._failed:
                    self._failed.add(name)
                    logger.warning(
                        "monitor backend %s failed to write events (%s); "
                        "training continues, further %s failures are "
                        "suppressed", name, e, name)
