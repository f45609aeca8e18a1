"""The Trial Runner (paper §2): profiles every ⟨model, parallelism,
GPU-count⟩ combination the Solver may choose.

Two interchangeable backends share one cache and result type:

- **empirical** — run 1–2 real minibatches of the job's step and time
  them (exactly the paper's mechanism; requires the device count to be
  available locally, e.g. under ``--xla_force_host_platform_device_count``).
- **analytic** — ``jit(...).lower().compile()`` the real step, then derive
  a three-term roofline time (compute / memory / collectives) from
  ``cost_analysis()`` + collective bytes parsed out of the HLO, against
  the target hardware's constants.  This is the CPU-container stand-in
  for running the two minibatches on real accelerators.

A third ``napkin`` mode skips lowering entirely (pure closed-form
roofline) — the cheap backend for benchmarks and the performance-model
layer's synthetic sweeps.

``profile_all`` supports three strategies (paper §2's <5% overhead
budget): ``"exhaustive"`` runs a real trial for every valid combo and
returns the legacy dict; ``"interpolate"`` runs trials only at a
geometric subset of counts per ⟨job, technique⟩ and returns a
:class:`~repro.core.perfmodel.PerfModel` of fitted throughput curves;
``"roofline"`` compiles each ⟨job, technique⟩ ONCE, converts the HLO's
op counts into a three-term roofline (compute / HBM / interconnect)
whose per-device-class efficiency coefficients are least-squares fit
from a handful of real calibration trials, and predicts every other
combo analytically — new device classes and 1000-combo search spaces
become essentially free to profile.  Either way, the outstanding real
trials run on a thread worker pool and land in a versioned,
atomically-written JSON cache (batched flushes: one rewrite per
``flush_every`` new profiles, temp-file + ``os.replace`` so a crash
mid-write can never corrupt the cache); the roofline calibration
coefficients persist in the same cache file.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..compile_cache import enable_compile_cache
from ..launch.hlo_analysis import analyze, link_seconds, scale_analysis
from ..models.params import abstract_params, param_count
from ..models.transformer import model_spec
from ..parallelism.base import Plan
from ..parallelism.build import BuiltJob
from .job import DEFAULT_CLASS, Job
from .library import ParallelismLibrary


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    flops: float          # peak FLOP/s per device (bf16)
    hbm_bw: float         # bytes/s per device
    link_bw: float        # bytes/s per device interconnect
    hbm_capacity: float   # bytes per device


HARDWARE = {
    # TPU v5e, per chip (Google Cloud documentation, "TPU v5e"): 197
    # TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM, 1,600 Gbit/s ICI
    "v5e": HardwareSpec("v5e", 197e12, 819e9, 1600e9 / 8, 16e9),
    # A100-40GB (the paper's p4d.24xlarge nodes)
    "a100": HardwareSpec("a100", 312e12, 1555e9, 600e9 / 8, 40e9),
    # V100-16GB (p3.16xlarge) — the mixed-fleet second class
    "v100": HardwareSpec("v100", 125e12, 900e9, 300e9 / 8, 16e9),
}


# jax ``Device.device_kind`` -> HARDWARE row
DEVICE_KINDS = {"TPU v5 lite": "v5e"}


def hardware_for_kind(device_kind: str) -> HardwareSpec:
    """The HARDWARE row of a JAX device kind; a kind not in the table is
    an error, never a default."""
    try:
        return HARDWARE[DEVICE_KINDS[device_kind]]
    except KeyError:
        raise ValueError(
            f"no HardwareSpec for device kind {device_kind!r}; known "
            f"kinds: {sorted(DEVICE_KINDS)}") from None


def local_hardware(hardware: Optional[HardwareSpec] = None
                   ) -> HardwareSpec:
    """The spec to plan with.  On TPU devices it is the local device's
    own row, and an explicit ``hardware`` that contradicts it is an
    error; elsewhere (CPU, virtual time) it is ``hardware``, A100 by
    default."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return hardware or HARDWARE["a100"]
    hw = hardware_for_kind(dev.device_kind)
    if hardware is not None and hardware != hw:
        raise ValueError(
            f"hardware {hardware.name!r} contradicts the local "
            f"{dev.device_kind!r} devices ({hw.name!r})")
    return hw


def is_out_of_memory(e: BaseException) -> bool:
    """True for the device's (or its compiler's) out-of-memory error —
    the one trial failure that makes a combo infeasible."""
    return isinstance(e, jax.errors.JaxRuntimeError) and \
        "RESOURCE_EXHAUSTED" in str(e)


def hardware_for_class(base: HardwareSpec, device_class) -> HardwareSpec:
    """Derive a per-class HardwareSpec from the cluster's reference
    hardware and a :class:`~repro.core.job.DeviceClass`: rates scale by
    ``speed_hint``; capacity comes from the class's HBM size."""
    s = float(device_class.speed_hint)
    return HardwareSpec(device_class.name, base.flops * s,
                        base.hbm_bw * s, base.link_bw * s,
                        device_class.hbm_per_gpu)

_COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^\n]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_DTYPE_BYTES = {
    "f32": 4, "f16": 2, "bf16": 2, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2,
}


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Sum output sizes of collective ops per kind from HLO text."""
    out: Dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:
                    numel *= int(d)
        out[kind] = out.get(kind, 0.0) + numel * nbytes
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


@dataclasses.dataclass
class Profile:
    job: str
    technique: str
    n_devices: int
    step_time_s: float
    mem_per_device: float
    feasible: bool
    source: str
    terms: Dict[str, float] = dataclasses.field(default_factory=dict)
    device_class: str = DEFAULT_CLASS

    def to_json(self):
        return dataclasses.asdict(self)


# v4: the cache also persists per-class roofline calibration fits —
# older caches (v3 and before) are discarded on load, not migrated: a
# v3 cache has no calibration section and re-running the trials is
# cheaper than guessing one
CACHE_VERSION = 4
PROFILE_MODES = ("analytic", "empirical", "napkin")
PROFILE_STRATEGIES = ("exhaustive", "interpolate", "roofline")


@dataclasses.dataclass
class ClassCalibration:
    """Per-device-class roofline efficiency fit.

    ``coef`` scales the three raw roofline features — the dominant
    ``max(compute, HBM)`` term, the interconnect term, and the fixed
    per-step launch latency — so ``t = coef · features``.  With fewer
    than 4 calibration points the fit collapses to a single shared
    efficiency (``coef[0] == coef[1] == coef[2]``): a scalar is all the
    data can support, and it is exactly the "machine balance" knob the
    roofline literature calibrates.  ``residual`` is the relative RMS
    error on the calibration points themselves (used as a confidence
    signal, not a held-out estimate).
    """
    device_class: str
    coef: Tuple[float, float, float]
    n_points: int
    residual: float
    mode: str

    def predict(self, features) -> float:
        t = float(np.dot(np.asarray(self.coef), np.asarray(features)))
        return max(t, 1e-9)

    def to_json(self):
        d = dataclasses.asdict(self)
        d["coef"] = list(self.coef)
        return d

    @classmethod
    def from_json(cls, d) -> "ClassCalibration":
        d = dict(d)
        d["coef"] = tuple(float(c) for c in d["coef"])
        return cls(**d)


def fit_calibration(device_class: str, points, mode: str
                    ) -> ClassCalibration:
    """Least-squares fit of per-class efficiency coefficients over the
    calibration trials.  ``points`` is a sequence of
    ``(features, observed_step_s)`` with 3-vector features.

    >=4 points fit the full 3-coefficient model (falling back when the
    solution goes non-physical, i.e. a negative dominant coefficient);
    fewer points — the default ~2 real trials per class — fit the
    single shared efficiency ``a = Σ x·y / Σ x·x`` over the summed
    features.
    """
    A = np.asarray([f for f, _ in points], dtype=float)
    y = np.asarray([t for _, t in points], dtype=float)
    coef = None
    if len(points) >= 4:
        full, *_ = np.linalg.lstsq(A, y, rcond=None)
        if np.all(np.isfinite(full)) and full[0] > 0 and \
                full[1] >= 0 and full[2] >= 0:
            coef = tuple(float(c) for c in full)
    if coef is None:
        x = A.sum(axis=1)
        denom = float(np.dot(x, x))
        a = float(np.dot(x, y) / denom) if denom > 0 else 1.0
        a = a if math.isfinite(a) and a > 0 else 1.0
        coef = (a, a, a)
    pred = A @ np.asarray(coef)
    rel = np.abs(pred - y) / np.maximum(np.abs(y), 1e-12)
    residual = float(np.sqrt(np.mean(rel ** 2))) if len(y) else math.inf
    return ClassCalibration(device_class, coef, len(points), residual,
                            mode)


class TrialRunner:
    def __init__(self, library: ParallelismLibrary,
                 hardware: Optional[HardwareSpec] = None,
                 cache_path: Optional[str] = None,
                 flush_every: int = 16,
                 hardware_by_class: Optional[Dict[str, HardwareSpec]] = None):
        self.library = library
        self.hw = hardware = local_hardware(hardware)
        # per-device-class hardware: the reference spec under "default";
        # register_class / hardware_by_class add mixed-fleet entries
        self.hw_by_class: Dict[str, HardwareSpec] = {DEFAULT_CLASS: hardware}
        self.hw_by_class.update(hardware_by_class or {})
        self.cache_path = cache_path
        self.flush_every = max(1, flush_every)
        self.trials = 0            # real trials computed by THIS runner
        self._dirty = 0            # new profiles since the last flush
        self._lock = threading.Lock()
        self._cache: Dict[Tuple[str, str, int, str, str], Profile] = {}
        # one compile per ⟨shape-identical job, technique, mesh shape⟩:
        # every analytic/roofline consumer reuses the lowered executable
        # + its parsed HLO analysis (empirical trials share the step
        # program itself through parallelism.build)
        self._compile_cache: Dict[Tuple, object] = {}
        self._analysis_cache: Dict[Tuple, Dict[str, float]] = {}
        # per-device-class roofline calibration (persisted in the cache)
        self.calibration: Dict[str, ClassCalibration] = {}
        # trials compile the steps the backends run later
        enable_compile_cache()
        if cache_path and os.path.exists(cache_path):
            self._load_cache(cache_path)

    def register_class(self, device_class) -> HardwareSpec:
        """Register a :class:`~repro.core.job.DeviceClass`, deriving its
        HardwareSpec from the reference hardware (idempotent; an
        explicit ``hardware_by_class`` entry wins)."""
        hw = self.hw_by_class.get(device_class.name)
        if hw is None:
            hw = hardware_for_class(self.hw, device_class)
            self.hw_by_class[device_class.name] = hw
        return hw

    def _class_hw(self, device_class: str) -> HardwareSpec:
        try:
            return self.hw_by_class[device_class]
        except KeyError:
            raise ValueError(
                f"unknown device class {device_class!r}; register it "
                f"(register_class / hardware_by_class); have "
                f"{list(self.hw_by_class)}") from None

    def _load_cache(self, path: str) -> None:
        """Versioned load: stale schemas (the legacy bare list, an older
        version number) and torn/corrupt files are silently discarded —
        a cache is a cache, never a crash."""
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError, UnicodeDecodeError):
            return
        if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
            return
        for rec in data.get("profiles", []):
            try:
                p = Profile(**rec)
            except TypeError:
                continue
            self._cache[(p.job, p.technique, p.n_devices, p.source,
                         p.device_class)] = p
        for dc, rec in (data.get("calibration") or {}).items():
            try:
                self.calibration[dc] = ClassCalibration.from_json(rec)
            except (TypeError, KeyError, ValueError):
                continue

    # ------------------------------------------------------------- public
    def profile(self, job: Job, technique: str, n_devices: int,
                mode: str = "analytic",
                device_class: str = DEFAULT_CLASS) -> Profile:
        if mode not in PROFILE_MODES:
            raise ValueError(f"unknown profiling mode {mode!r}; "
                             f"expected one of {PROFILE_MODES}")
        hw = self._class_hw(device_class)
        key = (job.name, technique, n_devices, mode, device_class)
        with self._lock:
            if key in self._cache:
                return self._cache[key]
        tech = self.library.get(technique)
        if not tech.search_space(job.cfg, n_devices):
            prof = Profile(job.name, technique, n_devices, float("inf"),
                           float("inf"), False, mode,
                           device_class=device_class)
            ran_trial = False
        else:
            with tracing.span("trial", job=job.name, technique=technique,
                              chips=n_devices, mode=mode):
                tracing.count("trial.runs")
                if mode == "empirical":
                    prof = self._profile_empirical(job, technique, n_devices,
                                                   hw, device_class)
                elif mode == "napkin":
                    prof = self._profile_napkin(job, technique, n_devices,
                                                hw, device_class)
                else:
                    prof = self._profile_analytic(job, technique, n_devices,
                                                  hw, device_class)
            ran_trial = True
        with self._lock:
            self._cache[key] = prof
            if ran_trial:
                self.trials += 1
            self._dirty += 1
            if self.cache_path and self._dirty >= self.flush_every:
                self._flush_locked()
        return prof

    def profile_all(self, jobs, gpu_counts, mode="analytic", *,
                    strategy: str = "exhaustive",
                    workers: Optional[int] = None,
                    anchor_ratio: float = 2.0,
                    classes=None,
                    calibration_trials: int = 2,
                    confidence_threshold: float = 0.3):
        """Profile a workload over ``gpu_counts``.

        ``strategy="exhaustive"`` runs a real trial at every valid
        (technique, count) and returns the legacy profile dict.

        ``strategy="interpolate"`` runs trials only at the geometric
        anchor subset per ⟨job, technique, device class⟩ (plus
        feasibility boundary counts) and returns a
        :class:`~repro.core.perfmodel.PerfModel` whose curves evaluate
        every other count.

        ``strategy="roofline"`` runs only ``calibration_trials`` real
        trials per device class to fit that class's roofline efficiency
        coefficients (persisted in the profile cache, so a later run —
        or a new device class with a cached fit — runs NO trials at
        all), predicts every combo from compiled-HLO op counts, and
        returns a :class:`~repro.core.perfmodel.PerfModel`.  Combos the
        prediction cannot be confident about — unfit collective
        patterns in the HLO, memory within a few percent of capacity,
        a poor calibration fit — fall back to real trials when their
        confidence drops below ``confidence_threshold`` (0 disables the
        fallback, 1 escalates everything).

        ``classes`` (a sequence of :class:`~repro.core.job.DeviceClass`)
        switches on heterogeneous profiling: every class gets its OWN
        anchor trials against its own hardware constants, counts are
        truncated to each class's capacity, and results are keyed
        ``(job, tech, device_class, g)`` (dict) / carry class-qualified
        curves (PerfModel).  Without it, the legacy single-class shapes
        are preserved exactly.
        """
        from .perfmodel import (PerfModel, ThroughputCurve,
                                select_anchor_counts)
        if strategy not in PROFILE_STRATEGIES:
            raise ValueError(
                f"unknown profiling strategy {strategy!r}; expected one "
                f"of {PROFILE_STRATEGIES}")
        counts = sorted(set(int(g) for g in gpu_counts))
        hetero = classes is not None
        if hetero:
            class_counts = {dc.name: [g for g in counts
                                      if g <= dc.total_gpus]
                            for dc in classes}
            for dc in classes:
                self.register_class(dc)
        else:
            class_counts = {DEFAULT_CLASS: counts}
        if strategy == "exhaustive":
            tasks = [(job, tech, g, dc)
                     for job in jobs for dc, cts in class_counts.items()
                     for tech, g in self.library.candidates(job.cfg, cts)]
            self._run_trials(tasks, mode, workers)
            self.flush()
            if hetero:
                return {(job.name, tech, dc, g):
                        self._cache[(job.name, tech, g, mode, dc)]
                        for job, tech, g, dc in tasks}
            return {(job.name, tech, g):
                    self._cache[(job.name, tech, g, mode, DEFAULT_CLASS)]
                    for job, tech, g, _ in tasks}
        if strategy == "roofline":
            return self._profile_all_roofline(
                jobs, counts, class_counts, mode, workers, hetero,
                calibration_trials, confidence_threshold)
        plan: Dict[Tuple[str, str, str], Tuple[Job, list, list]] = {}
        tasks = []
        for job in jobs:
            for dc, cts in class_counts.items():
                for tech_name, tech in self.library.items():
                    valid = [g for g in cts
                             if tech.search_space(job.cfg, g)]
                    if not valid:
                        continue
                    anchors = select_anchor_counts(valid, anchor_ratio)
                    plan[(job.name, tech_name, dc)] = (job, valid, anchors)
                    tasks.extend((job, tech_name, g, dc) for g in anchors)
        self._run_trials(tasks, mode, workers)
        self.flush()
        curves = {}
        for (jname, tech_name, dc), (job, valid, anchors) in plan.items():
            profs = {g: self._cache[(jname, tech_name, g, mode, dc)]
                     for g in anchors}
            curve = ThroughputCurve(
                jname, tech_name, self._class_hw(dc).hbm_capacity, profs,
                valid=valid, domain=class_counts[dc], device_class=dc)
            if hetero:
                curves[(jname, tech_name, dc)] = curve
            else:
                curves[(jname, tech_name)] = curve
        return PerfModel(curves, counts,
                         counts_by_class=class_counts if hetero else None)

    def _run_trials(self, tasks, mode: str, workers: Optional[int]) -> None:
        """Run the outstanding real trials, in parallel where safe.

        Empirical trials time real minibatches, so they must not share
        the machine — those always run serially.  Analytic/napkin trials
        are compile/arithmetic work and fan out over a thread pool.
        """
        seen = set()
        todo = []
        for job, tech, g, dc in tasks:
            key = (job.name, tech, g, dc)
            if key in seen:
                continue
            seen.add(key)
            todo.append((job, tech, g, dc))
        if workers is None:
            workers = 1 if mode == "empirical" else \
                min(8, os.cpu_count() or 1)
        if workers <= 1 or len(todo) <= 1 or mode == "empirical":
            for job, tech, g, dc in todo:
                self.profile(job, tech, g, mode, device_class=dc)
            return
        with ThreadPoolExecutor(max_workers=workers) as pool:
            futs = [pool.submit(self.profile, job, tech, g, mode,
                                device_class=dc)
                    for job, tech, g, dc in todo]
            for f in futs:
                f.result()

    # ------------------------------------------------- roofline strategy
    def _calibration_combos(self, combos, k: int, mode: str):
        """Pick the ~k ⟨job, technique, count⟩ combos whose real trials
        anchor one class's calibration: round-robin over distinct
        (job, technique) pairs, alternating each pair's largest and
        smallest valid count so the fit sees both the collective-heavy
        and the single-device regime.  Empirical trials can only run on
        counts the local pool hosts."""
        local = len(jax.devices())
        picked, out = set(), []
        i = 0
        while len(out) < max(1, k) and i < 4 * max(1, len(combos)):
            job, tech_name, valid = combos[i % len(combos)]
            i += 1
            cts = [g for g in valid if g <= local] \
                if mode == "empirical" else valid
            if not cts:
                continue
            g = cts[-1] if len(out) % 2 == 0 else cts[0]
            key = (job.name, tech_name, g)
            if key in picked:
                continue
            picked.add(key)
            out.append((job, tech_name, g))
        return out

    def _profile_all_roofline(self, jobs, counts, class_counts, mode,
                              workers, hetero, calibration_trials,
                              confidence_threshold):
        from .perfmodel import PerfModel, ThroughputCurve
        plan: Dict[Tuple[str, str, str], Tuple[Job, list]] = {}
        by_class: Dict[str, list] = {}
        for job in jobs:
            for dc, cts in class_counts.items():
                for tech_name, tech in self.library.items():
                    valid = [g for g in cts
                             if tech.search_space(job.cfg, g)]
                    if not valid:
                        continue
                    plan[(job.name, tech_name, dc)] = (job, valid)
                    by_class.setdefault(dc, []).append(
                        (job, tech_name, valid))
        # ---- 1) per-class calibration: reuse a persisted fit when one
        # exists for this mode, otherwise run the calibration trials
        calib: Dict[str, list] = {}
        tasks = []
        for dc, combos in by_class.items():
            cached = self.calibration.get(dc)
            if cached is not None and cached.mode == mode and \
                    cached.n_points >= 1:
                continue
            calib[dc] = self._calibration_combos(
                combos, calibration_trials, mode)
            tasks.extend((job, tech_name, g, dc)
                         for job, tech_name, g in calib[dc])
        self._run_trials(tasks, mode, workers)
        for dc, picked in calib.items():
            hw = self._class_hw(dc)
            pts = []
            for job, tech_name, g in picked:
                p = self._cache[(job.name, tech_name, g, mode, dc)]
                if not (math.isfinite(p.step_time_s)
                        and p.step_time_s > 0):
                    continue
                tech_plan = self.library.get(tech_name).plan(job.cfg, g)
                feats, _, _ = self._raw_features(job, tech_plan, hw, mode)
                pts.append((feats, p.step_time_s))
            self.calibration[dc] = fit_calibration(dc, pts, mode) if pts \
                else ClassCalibration(dc, (1.0, 1.0, 1.0), 0,
                                      float("inf"), mode)
        # ---- 2) predict every combo; collect low-confidence escalations
        anchors: Dict[Tuple[str, str, str], Dict[int, Profile]] = {}
        escalate = []
        n_predicted = 0
        for (jname, tech_name, dc), (job, valid) in plan.items():
            hw = self._class_hw(dc)
            cal = self.calibration[dc]
            a: Dict[int, Profile] = {}
            for g in valid:
                real = self._cache.get((jname, tech_name, g, mode, dc))
                if real is not None:
                    a[g] = real
                    continue
                pred = self._predict_roofline(job, tech_name, g, hw, dc,
                                              cal, mode)
                hostable = mode != "empirical" or g <= len(jax.devices())
                if pred.terms["confidence"] < confidence_threshold \
                        and hostable:
                    escalate.append((job, tech_name, g, dc))
                a[g] = pred
                n_predicted += 1
            anchors[(jname, tech_name, dc)] = a
        # ---- 3) escalated combos get REAL trials that replace their
        # predictions (and land in the persistent cache)
        self._run_trials(escalate, mode, workers)
        for job, tech_name, g, dc in escalate:
            anchors[(job.name, tech_name, dc)][g] = \
                self._cache[(job.name, tech_name, g, mode, dc)]
        self.roofline_stats = {
            "predicted": n_predicted - len(escalate),
            "escalated": len(escalate),
            "calibration_trials": sum(len(v) for v in calib.values()),
        }
        # predictions are cached too (source="roofline", so they can
        # never be mistaken for a real trial of any mode)
        with self._lock:
            for (jname, tech_name, dc), a in anchors.items():
                for g, p in a.items():
                    if p.source == "roofline":
                        self._cache[(jname, tech_name, g, "roofline",
                                     dc)] = p
                        self._dirty += 1
        self.flush()
        curves = {}
        for (jname, tech_name, dc), (job, valid) in plan.items():
            curve = ThroughputCurve(
                jname, tech_name, self._class_hw(dc).hbm_capacity,
                anchors[(jname, tech_name, dc)], valid=valid,
                domain=class_counts[dc], device_class=dc)
            if hetero:
                curves[(jname, tech_name, dc)] = curve
            else:
                curves[(jname, tech_name)] = curve
        return PerfModel(curves, counts,
                         counts_by_class=class_counts if hetero else None)

    # --------------------------------------------------------- empirical
    def _profile_empirical(self, job: Job, technique: str, n_devices: int,
                           hw: HardwareSpec, device_class: str) -> Profile:
        from ..configs import concrete_batch
        if n_devices > len(jax.devices()):
            raise RuntimeError(
                f"empirical profiling needs {n_devices} local devices")
        tech = self.library.get(technique)
        try:
            with tracing.span("trial.init"):
                plan = tech.plan(job.cfg, n_devices)
                built = self._built_job(job, plan)
                params, opt = built.init(jax.random.PRNGKey(0))
                batch = built.place_batch(
                    concrete_batch(job.cfg, job.batch_size, job.seq_len))
            with tracing.span("trial.compile"):
                step = self._compiled_step(job, plan, (params, opt, batch))
            with tracing.span("trial.steps"):
                # 1 warmup + 2 timed minibatches, per the paper
                params, opt, _ = step(params, opt, batch)
                jax.block_until_ready(params)
                t0 = time.perf_counter()
                for _ in range(2):
                    params, opt, _ = step(params, opt, batch)
                jax.block_until_ready(params)
                dt = (time.perf_counter() - t0) / 2
        except jax.errors.JaxRuntimeError as e:
            # a step that does not fit the device is an infeasible
            # choice; any other failure is a bug and propagates
            if not is_out_of_memory(e):
                raise
            return self._out_of_memory(job, technique, n_devices,
                                       "empirical", device_class)
        mem = self._compiled_mem(step)
        return Profile(job.name, technique, n_devices, dt, mem,
                       mem <= hw.hbm_capacity, "empirical",
                       device_class=device_class)

    # ---------------------------------------------------------- analytic
    def _profile_analytic(self, job: Job, technique: str, n_devices: int,
                          hw: HardwareSpec, device_class: str) -> Profile:
        """Lower + compile the real step and read cost_analysis / HLO
        collectives.  A mesh the local devices cannot host falls back to
        the napkin model, labelled ``"napkin"``."""
        tech = self.library.get(technique)
        plan = tech.plan(job.cfg, n_devices)
        if n_devices > len(jax.devices()):
            return self._finish(job, technique, n_devices,
                                self._roofline_napkin(job, plan, hw),
                                "napkin", hw, device_class)
        try:
            terms = self._roofline_from_compile(job, plan, hw)
        except jax.errors.JaxRuntimeError as e:
            if not is_out_of_memory(e):
                raise
            return self._out_of_memory(job, technique, n_devices,
                                       "analytic", device_class)
        return self._finish(job, technique, n_devices, terms, "analytic",
                            hw, device_class)

    @staticmethod
    def _out_of_memory(job: Job, technique: str, n_devices: int,
                       source: str, device_class: str) -> Profile:
        tracing.count("trial.refused")
        return Profile(job.name, technique, n_devices, float("inf"),
                       float("inf"), False, source, {"out_of_memory": 1.0},
                       device_class=device_class)

    def _profile_napkin(self, job: Job, technique: str, n_devices: int,
                        hw: HardwareSpec, device_class: str) -> Profile:
        """Closed-form roofline only — no lowering/compilation.  The
        cheap deterministic backend for benchmark sweeps."""
        tech = self.library.get(technique)
        plan = tech.plan(job.cfg, n_devices)
        return self._finish(job, technique, n_devices,
                            self._roofline_napkin(job, plan, hw),
                            "napkin", hw, device_class)

    def _finish(self, job: Job, technique: str, n_devices: int,
                terms: Dict[str, float], source: str,
                hw: HardwareSpec, device_class: str) -> Profile:
        tech = self.library.get(technique)
        mem = terms.pop("mem_per_device")
        # roofline: compute and memory overlap with collectives imperfectly;
        # take max(compute, memory) + collective (conservative serial comm)
        t = max(terms["compute_s"], terms["memory_s"]) + terms["collective_s"]
        t *= tech.step_overhead()
        terms["modeled_step_s"] = t
        return Profile(job.name, technique, n_devices, t, mem,
                       mem <= hw.hbm_capacity, source, terms,
                       device_class=device_class)

    def _mem_estimate(self, job: Job, plan: Plan) -> float:
        """Params + AdamW state + activation estimate, per device."""
        tech = self.library.get(plan.technique)
        n_params = param_count(model_spec(job.cfg))
        # fp32 params + mu + nu = 12 bytes/param, sharded per technique
        state = 12.0 * n_params * tech.memory_fraction(job.cfg, plan.n_devices)
        act = self._activation_bytes(job, plan)
        return state + act

    def _activation_bytes(self, job: Job, plan: Plan) -> float:
        cfg = job.cfg
        b, s = job.batch_size, job.seq_len
        if plan.rules.get("batch"):
            b = max(1, b // dict(plan.mesh_axes).get(plan.rules["batch"], 1))
        per_layer = 2.0 * b * s * cfg.d_model * 6  # bf16, ~6 tensors/block
        layers = cfg.num_layers / plan.stages
        if plan.remat:
            return 2.0 * b * s * cfg.d_model * layers  # one residual/layer
        return per_layer * layers

    # ------------------------------------------------ compile memoization
    def _shape_key(self, job: Job, technique: str, mesh_shape) -> Tuple:
        """Jobs that lower to the same program share one compile: the
        step's HLO depends on the model shape, the batch shape, and the
        technique's mesh — not on the job's name, lr, or seed."""
        cfg = job.cfg
        return (cfg.name, cfg.d_model, cfg.num_layers, job.batch_size,
                job.seq_len, technique, tuple(mesh_shape))

    def _built_job(self, job: Job, plan: Plan) -> BuiltJob:
        """The job's BuiltJob; shape-identical jobs share its program
        (``parallelism.build``)."""
        return BuiltJob(job.cfg, plan, job.opt_cfg,
                        devices=jax.devices()[:plan.n_devices])

    def _compiled_step(self, job: Job, plan: Plan, args=None):
        """Memoized ``lower().compile()`` of the real step per
        ⟨job-shape, technique, mesh-shape⟩, shared by empirical trials,
        the analytic roofline, the HLO analyzer, and the roofline
        strategy.  An empirical trial passes its concrete ``(params,
        opt_state, batch)``: lowered from those, the program is the one
        the execution backends compile, so they find it in the
        persistent compilation cache (the abstract lowering numbers its
        functions differently and misses)."""
        key = self._shape_key(job, plan.technique, plan.mesh_shape)
        with self._lock:
            compiled = self._compile_cache.get(key)
        if compiled is not None:
            return compiled
        from ..configs import concrete_batch
        n = plan.n_devices
        if n > len(jax.devices()):
            raise RuntimeError("not enough local devices to lower")
        built = self._built_job(job, plan)
        if args is None:
            spec = model_spec(job.cfg)
            p_abs = abstract_params(spec, jnp.float32)
            o_abs = {"mu": abstract_params(spec, jnp.float32),
                     "nu": abstract_params(spec, jnp.float32),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
            batch = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                concrete_batch(job.cfg, job.batch_size, job.seq_len))
            args = (p_abs, o_abs, batch)
        compiled = built.step.lower(*args).compile()
        with self._lock:
            self._compile_cache.setdefault(key, compiled)
        return compiled

    def _hlo_analysis(self, job: Job, plan: Plan) -> Dict[str, float]:
        """Memoized loop-aware HLO analysis of the compiled step (see
        :mod:`repro.launch.hlo_analysis`)."""
        key = self._shape_key(job, plan.technique, plan.mesh_shape)
        with self._lock:
            a = self._analysis_cache.get(key)
        if a is None:
            a = analyze(self._compiled_step(job, plan).as_text())
            with self._lock:
                self._analysis_cache.setdefault(key, a)
        return a

    def _roofline_from_compile(self, job: Job, plan: Plan,
                               hw: HardwareSpec):
        compiled = self._compiled_step(job, plan)
        n = plan.n_devices
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0)) / n
        bytes_acc = float(cost.get("bytes accessed", 0.0)) / n
        coll = collective_bytes_from_hlo(compiled.as_text())
        coll_bytes = coll["total"] / n
        mem = self._compiled_mem(compiled)
        return {
            "compute_s": flops / hw.flops,
            "memory_s": bytes_acc / hw.hbm_bw,
            "collective_s": coll_bytes / hw.link_bw,
            "hlo_flops": flops * n,
            "collective_bytes": coll["total"],
            "mem_per_device": mem,
        }

    @staticmethod
    def _compiled_mem(compiled) -> float:
        """Bytes per device of the compiled (per-device SPMD) program."""
        ma = compiled.memory_analysis()
        return float(ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                     ma.output_size_in_bytes)

    def _utilization(self, job: Job, plan: Plan) -> float:
        """MXU/SM utilization model: saturates with per-device tokens;
        the knee sits higher for narrow models (small matmuls need more
        batch to fill the MXU/SMs) — this is what makes right-sizing
        matter.  TP shards the *width*, so its effective matmul width
        is d/g."""
        cfg = job.cfg
        g = plan.n_devices
        tokens = job.batch_size * job.seq_len
        tok_dev = tokens if plan.technique == "tp" else tokens / g
        d_eff = cfg.d_model / g if plan.technique == "tp" else cfg.d_model
        knee = 8192.0 * 2048.0 / (d_eff + 2048.0)
        util = (d_eff / (d_eff + 1024.0)) * (tok_dev / (tok_dev + knee))
        return max(util, 0.02)

    @staticmethod
    def _fixed_step_s(cfg, g: int) -> float:
        """Fixed per-step overhead: launch + per-layer collective
        latency, growing with device count."""
        return 2e-3 + 1e-4 * g + cfg.num_layers * 5e-5 * np.log2(max(g, 2))

    def _napkin_raw(self, job: Job, plan: Plan,
                    hw: HardwareSpec) -> Dict[str, float]:
        """6·N·D closed-form raw roofline terms (no lowering), with the
        fixed per-step latency split out so the calibration fit can
        weigh it separately."""
        cfg = job.cfg
        n_params = param_count(model_spec(cfg))
        if cfg.is_moe:
            n_active = n_params * (cfg.moe.top_k / cfg.moe.num_experts)
        else:
            n_active = n_params
        g = plan.n_devices
        tokens = job.batch_size * job.seq_len
        util = self._utilization(job, plan)
        flops = 6.0 * n_active * tokens / g
        compute_s = flops / (hw.flops * util)
        fixed_s = self._fixed_step_s(cfg, g)
        # bytes: params read 3x (fwd, bwd, opt) + activations
        tech = self.library.get(plan.technique)
        bytes_acc = (12.0 * n_params * tech.memory_fraction(cfg, g)
                     + self._activation_bytes(job, plan) * 4)
        coll = 4.0 * n_params / max(g, 1) if g > 1 else 0.0  # grad reduce
        return {
            "compute_s": compute_s,
            "memory_s": bytes_acc / hw.hbm_bw,
            "collective_s": coll / hw.link_bw,
            "fixed_s": fixed_s,
            "hlo_flops": flops * g,
            "collective_bytes": coll * g,
            "mem_per_device": self._mem_estimate(job, plan),
            "utilization": util,
        }

    def _roofline_napkin(self, job: Job, plan: Plan,
                         hw: HardwareSpec) -> Dict[str, float]:
        """6·N·D flops model when compile-based profiling is unavailable.

        Includes the two effects that make right-sizing matter (and that
        Saturn exploits): (a) MXU/SM utilization collapses when the
        per-device work gets small (tiny models on many GPUs waste
        capacity), and (b) fixed per-step latency (launch + collective
        setup) grows with device count."""
        raw = self._napkin_raw(job, plan, hw)
        return {
            "compute_s": raw["compute_s"] + raw["fixed_s"],
            "memory_s": raw["memory_s"],
            "collective_s": raw["collective_s"],
            "hlo_flops": raw["hlo_flops"],
            "collective_bytes": raw["collective_bytes"],
            "mem_per_device": raw["mem_per_device"],
            "utilization": raw["utilization"],
        }

    # ---------------------------------------------------------- roofline
    #
    # strategy="roofline": one compile per ⟨job-shape, technique⟩, op
    # counts from the loop-aware HLO analyzer scaled across device
    # counts, per-class efficiency coefficients fit from a handful of
    # real calibration trials — every other combo is predicted, not run.

    def _raw_features(self, job: Job, plan: Plan, hw: HardwareSpec,
                      mode: str = "analytic"
                      ) -> Tuple[Tuple[float, float, float],
                                 Dict[str, float], List[str]]:
        """Raw roofline features for one combo: ``(dominant, link,
        fixed)`` seconds (technique overhead folded in), the term dict
        for the Profile record, and any UNFIT collective kinds (present
        in the HLO, absent from the ring model — a low-confidence
        signal).

        Op counts come from ONE memoized compile per ⟨job-shape,
        technique⟩, rescaled to this count (`scale_analysis`); when no
        local mesh can host even a base compile — or under
        ``mode="napkin"``, whose simulated ground truth is the
        closed-form model itself and where a real compile would defeat
        the simulation's purpose — the closed-form napkin terms stand
        in.
        """
        g = plan.n_devices
        unfit: List[str] = []
        base = None if mode == "napkin" \
            else self._hlo_base_analysis(job, plan)
        if base is not None:
            n_base, analysis = base
            scaled = scale_analysis(analysis, n_base, g)
            util = self._utilization(job, plan)
            compute_s = scaled["flops"] / (hw.flops * util)
            memory_s = scaled["bytes_written"] / hw.hbm_bw
            collective_s, unfit = link_seconds(
                scaled["collectives"], g, hw.link_bw) if g > 1 \
                else (0.0, [])
            terms = {"hlo_flops": scaled["flops"] * g,
                     "collective_bytes": scaled["collectives"]["total"],
                     "utilization": util, "hlo_base_n": float(n_base)}
        else:
            raw = self._napkin_raw(job, plan, hw)
            compute_s = raw["compute_s"]
            memory_s = raw["memory_s"]
            collective_s = raw["collective_s"]
            terms = {"hlo_flops": raw["hlo_flops"],
                     "collective_bytes": raw["collective_bytes"],
                     "utilization": raw["utilization"]}
        fixed_s = self._fixed_step_s(job.cfg, g)
        ovh = self.library.get(plan.technique).step_overhead()
        feats = (ovh * max(compute_s, memory_s), ovh * collective_s,
                 ovh * fixed_s)
        terms.update({"compute_s": compute_s, "memory_s": memory_s,
                      "collective_s": collective_s, "fixed_s": fixed_s})
        return feats, terms, unfit

    def _hlo_base_analysis(self, job: Job, plan: Plan
                           ) -> Optional[Tuple[int, Dict[str, float]]]:
        """The ⟨base count, HLO analysis⟩ this combo's raw terms scale
        from: the combo's own mesh when the local pool can host it,
        otherwise the largest hostable valid count for the technique
        (compiled once, memoized).  None when nothing can be lowered."""
        tech = self.library.get(plan.technique)
        local = len(jax.devices())
        seen = set()
        for n in [plan.n_devices] + \
                list(range(min(local, plan.n_devices), 0, -1)):
            if n in seen or n > local or \
                    not tech.search_space(job.cfg, n):
                continue
            seen.add(n)
            base_plan = plan if n == plan.n_devices \
                else tech.plan(job.cfg, n)
            try:
                return n, self._hlo_analysis(job, base_plan)
            except jax.errors.JaxRuntimeError as e:
                if not is_out_of_memory(e):
                    raise
        return None

    def _predict_roofline(self, job: Job, technique: str, n_devices: int,
                          hw: HardwareSpec, device_class: str,
                          cal: ClassCalibration,
                          mode: str = "analytic") -> Profile:
        """One predicted Profile (``source="roofline"``) with a
        confidence term the fallback knob acts on."""
        tech = self.library.get(technique)
        plan = tech.plan(job.cfg, n_devices)
        feats, terms, unfit = self._raw_features(job, plan, hw, mode)
        t = cal.predict(feats)
        mem = self._mem_estimate(job, plan)
        confidence = 1.0
        if cal.n_points < 2:
            confidence *= 0.5
        if cal.residual > 0.25:
            confidence *= 0.5
        if unfit:
            confidence *= 0.25
            terms["unfit_collectives"] = float(len(unfit))
        # memory-boundary cases: the fit-or-doesn't-fit call is made on
        # an ESTIMATE — within a few percent of capacity the analytic
        # answer is a coin flip, so flag it for escalation
        if hw.hbm_capacity > 0 and \
                0.95 <= mem / hw.hbm_capacity <= 1.05:
            confidence *= 0.25
        terms["confidence"] = confidence
        terms["modeled_step_s"] = t
        return Profile(job.name, technique, n_devices, t, mem,
                       mem <= hw.hbm_capacity, "roofline", terms,
                       device_class=device_class)

    # -------------------------------------------------------------- misc
    def flush(self) -> None:
        """Write the cache to disk now (atomic temp-file + rename)."""
        with self._lock:
            self._flush_locked()

    # flushes are batched, so direct profile() callers could otherwise
    # lose the tail of their (possibly expensive empirical) trials when
    # the runner goes away without an explicit flush()
    def __enter__(self) -> "TrialRunner":
        return self

    def __exit__(self, *exc) -> None:
        self.flush()

    def __del__(self):
        try:
            self.flush()
        except Exception:
            pass               # interpreter teardown: best effort only

    def _flush_locked(self) -> None:
        if not self.cache_path or not self._dirty:
            return
        path = os.path.abspath(self.cache_path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {"version": CACHE_VERSION,
                   "profiles": [p.to_json() for p in self._cache.values()],
                   "calibration": {dc: c.to_json()
                                   for dc, c in self.calibration.items()}}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        self._dirty = 0

    # back-compat alias (pre-batching callers)
    _flush = flush
