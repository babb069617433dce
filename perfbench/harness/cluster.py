"""A cluster deployment (a ``cluster_planner`` configuration file): its job
types, the seed's traces, and the scenario columns of a block of grid rows,
as the program takes them and as the plain reference takes them."""

from __future__ import annotations

import math

import numpy as np

from perfbench.harness.jobs import _coerce
from perfbench.harness.stats import apportion
from perfbench.reference import hadoop_params as ref_params
from perfbench.reference import wave_ref

__all__ = ["Fleet", "job_kind"]


def job_kind(t: dict, cfg: dict) -> tuple[dict, dict]:
    """(Table-1 parameters, profile statistics) of one job type from its
    median input, shuffle and output bytes: maps over the input in splits,
    reducers by bytes of input, selectivities as ratios of the sizes."""
    inp, shuf, out = t["input_bytes"], t["shuffle_bytes"], t["output_bytes"]
    maps = max(1, math.ceil(inp / cfg["split_size"]))
    if shuf > 0:
        reds = min(int(cfg["max_reducers"]), max(1, math.ceil(inp / cfg["bytes_per_reducer"])))
        map_sel, red_sel = shuf / inp, out / shuf
    else:
        reds, map_sel, red_sel = 0, out / inp, 1.0
    params = {"pNumMappers": float(maps), "pNumReducers": float(reds),
              "pSplitSize": inp / maps}
    stats = {"sInputPairWidth": cfg["input_pair_width"], "sMapSizeSel": map_sel,
             "sMapPairsSel": map_sel, "sReduceSizeSel": red_sel, "sReducePairsSel": red_sel}
    return params, stats


class Fleet:
    """The job types with at least one job in a trace, and ``traces``
    unit-rate traces of ``jobs_per_trace`` jobs.  Every seed gets the same
    jobs and the same set of exponential gaps, in its own order."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.grid = {k: [float(x) for x in v] for k, v in cfg["grid"].items()}
        n = int(cfg["jobs_per_trace"])
        types = cfg["job_types"]
        counts = apportion([t["jobs"] for t in types.values()], n)
        self.kinds = [(name, *job_kind(t, cfg), c)
                      for (name, t), c in zip(types.items(), counts) if c > 0]
        pool = np.repeat(np.arange(len(self.kinds)), [k[3] for k in self.kinds])
        q = (np.arange(n) + 0.5) / n
        gaps0 = -np.log1p(-q)
        rng = np.random.default_rng([seed, 41])
        self.traces = []                       # [(submit_s, kind index)] per trace
        for _ in range(int(cfg["traces"])):
            order = rng.permutation(pool)
            gaps = rng.permutation(gaps0)
            times = np.cumsum(gaps) - gaps[0]
            self.traces.append(list(zip(times.tolist(), order.tolist())))

    def program_evaluator(self):
        """The program's :class:`repro.cluster.ClusterEvaluator` on these
        traces, at its defaults otherwise."""
        from repro.cluster import ClusterEvaluator
        from repro.cluster.workload import JobArrival, JobClass, WorkloadTrace
        from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats

        costs = _coerce(CostFactors, self.cfg["costs"])
        classes = [JobClass(name=name, params=_coerce(HadoopParams, p),
                            stats=_coerce(ProfileStats, s), costs=costs, weight=float(c))
                   for name, p, s, c in self.kinds]
        traces = [WorkloadTrace(tuple(JobArrival(j, classes[k], t)
                                      for j, (t, k) in enumerate(tr)))
                  for tr in self.traces]
        return ClusterEvaluator(classes, traces=traces, objective=self.cfg["objective"])

    def reference_traces(self):
        """The traces as ``(submit_s, (params, stats, costs))`` of the copied
        oracle's dataclasses."""
        costs = _coerce(ref_params.CostFactors, self.cfg["costs"])
        kinds = [(_coerce(ref_params.HadoopParams, p), _coerce(ref_params.ProfileStats, s),
                  costs) for _, p, s, _ in self.kinds]
        return [[(t, kinds[k]) for t, k in tr] for tr in self.traces]

    def scenarios(self, rows: dict, traces) -> list[dict]:
        """Per trace, the reference's scenario columns for grid rows
        ``rows`` (columns of equal length): ``(R, J)`` job columns and
        ``(R,)`` cluster columns."""
        nodes = np.round(rows["pNumNodes"])
        out = []
        for tr in traces:
            times = np.asarray([t for t, _ in tr], dtype=np.float64)
            per_node = {n: np.asarray([wave_ref.task_times(*k, int(n)) for _, k in tr])
                        for n in np.unique(nodes)}
            tt = np.stack([per_node[n] for n in nodes])            # (R, J, 3)
            out.append({
                "arrival": times[None, :] / rows["arrivalRate"][:, None],
                "n_maps": np.tile([k[0].pNumMappers for _, k in tr], (len(nodes), 1)),
                "n_reds": np.tile([k[0].pNumReducers for _, k in tr], (len(nodes), 1)),
                "map_dur": tt[:, :, 0], "shuffle": tt[:, :, 1], "red_work": tt[:, :, 2],
                "map_slots": nodes * np.round(rows["pMaxMapsPerNode"]),
                "red_slots": nodes * np.round(rows["pMaxRedPerNode"]),
                "fair": rows["schedPolicy"], "slowstart": rows["pReduceSlowstart"],
            })
        return out
