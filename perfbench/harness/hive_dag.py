"""A Hive deployment of a query benchmark (a ``cluster_planner_dag``
configuration file): each query's stages as MapReduce jobs, the streams of
the throughput test as a DAG of jobs, the seed's traces, and the scenario
columns of a block of grid rows, as the program takes them and as the plain
reference takes them."""

from __future__ import annotations

import numpy as np

from perfbench.harness.cluster import job_kind
from perfbench.harness.jobs import _coerce
from perfbench.reference import hadoop_params as ref_params
from perfbench.reference import wave_ref

__all__ = ["HiveStreams"]


def _stage_kinds(cfg: dict) -> list[tuple]:
    """``(name, Table-1 parameters, profile statistics, parents)`` of every
    query stage, parents as positions in the same list.  A stage's input is
    the bytes of the tables it reads plus its parents' output."""
    table_bytes = {t: v["rows"] * v["row_bytes"] for t, v in cfg["tables"].items()}
    out, out_bytes = [], []
    for q, query in cfg["queries"].items():
        base = len(out)
        for i, s in enumerate(query["stages"]):
            parents = [base + p for p in s["parents"]]
            inp = sum(table_bytes[t] for t in s["reads"]) + sum(out_bytes[p] for p in parents)
            shuf = inp * s["map_sel"]
            params, stats = job_kind({"input_bytes": inp, "shuffle_bytes": shuf,
                                      "output_bytes": shuf * s["red_sel"]}, cfg)
            if s.get("order_by"):
                params["pNumReducers"] = 1.0
            out.append((f"{q}.s{i}", params, stats, parents))
            out_bytes.append(shuf * s["red_sel"])
    return out


class HiveStreams:
    """The stages of every query, and ``traces`` traces of ``streams``
    query streams.  In a trace each stream runs the queries in a seeded
    order; every job is submitted at 0 and held by its edges: a stage by
    its parents in the query, a query's root stages by the last stage of
    the stream's previous query.  Job ids run by query position, then
    stream, then stage, so every parent has a lower id than its child."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.grid = {k: [float(x) for x in v] for k, v in cfg["grid"].items()}
        self.kinds = _stage_kinds(cfg)
        queries = list(cfg["queries"])
        first = np.cumsum([0] + [len(cfg["queries"][q]["stages"]) for q in queries])
        rng = np.random.default_rng([seed, 47])
        self.traces = []                       # [(kind index, parent job ids)] per trace
        for _ in range(int(cfg["traces"])):
            orders = [rng.permutation(len(queries)) for _ in range(int(cfg["streams"]))]
            jobs, last = [], [None] * len(orders)
            for pos in range(len(queries)):
                for s, order in enumerate(orders):
                    q = order[pos]
                    ids = {}
                    for k in range(first[q], first[q + 1]):
                        parents = [ids[p] for p in self.kinds[k][3]]
                        if not parents and last[s] is not None:
                            parents = [last[s]]
                        ids[k] = len(jobs)
                        jobs.append((k, parents))
                    last[s] = len(jobs) - 1
            self.traces.append(jobs)

    def program_evaluator(self):
        """The program's :class:`repro.cluster.ClusterEvaluator` on these
        traces with the configuration's objective, at its defaults
        otherwise."""
        from repro.cluster import ClusterEvaluator
        from repro.cluster.workload import JobArrival, JobClass, WorkloadTrace
        from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats

        costs = _coerce(CostFactors, self.cfg["costs"])
        classes = [JobClass(name=name, params=_coerce(HadoopParams, p),
                            stats=_coerce(ProfileStats, s), costs=costs)
                   for name, p, s, _ in self.kinds]
        traces = [WorkloadTrace(tuple(
            JobArrival(j, classes[k], 0.0, tuple((p, "barrier") for p in parents))
            for j, (k, parents) in enumerate(tr))) for tr in self.traces]
        return ClusterEvaluator(classes, traces=traces, objective=self.cfg["objective"])

    def reference_traces(self):
        """The traces as ``[((params, stats, costs), parent job ids)]`` of
        the copied oracle's dataclasses."""
        costs = _coerce(ref_params.CostFactors, self.cfg["costs"])
        kinds = [(_coerce(ref_params.HadoopParams, p), _coerce(ref_params.ProfileStats, s),
                  costs) for _, p, s, _ in self.kinds]
        return [[(kinds[k], parents) for k, parents in tr] for tr in self.traces]

    def scenarios(self, rows: dict, traces) -> list[dict]:
        """Per trace, the reference's scenario columns for grid rows
        ``rows`` (columns of equal length): ``(R, J)`` job columns, ``(R,)``
        cluster and network columns, and the ``(J, P)`` parents (-1 where a
        job has fewer)."""
        nodes = np.round(rows["pNumNodes"])
        out = []
        for tr in traces:
            per_node = {n: np.asarray([wave_ref.task_times(*k, int(n)) for k, _ in tr])
                        for n in np.unique(nodes)}
            tt = np.stack([per_node[n] for n in nodes])            # (R, J, 3)
            n_par = max(1, max(len(p) for _, p in tr))
            dep = np.full((len(tr), n_par), -1, dtype=np.int64)
            for j, (_, parents) in enumerate(tr):
                dep[j, :len(parents)] = parents
            out.append({
                "arrival": np.zeros((len(nodes), len(tr))),
                "n_maps": np.tile([k[0].pNumMappers for k, _ in tr], (len(nodes), 1)),
                "n_reds": np.tile([k[0].pNumReducers for k, _ in tr], (len(nodes), 1)),
                "map_dur": tt[:, :, 0], "shuffle": tt[:, :, 1], "red_work": tt[:, :, 2],
                "map_slots": nodes * np.round(rows["pMaxMapsPerNode"]),
                "red_slots": nodes * np.round(rows["pMaxRedPerNode"]),
                "fair": rows["schedPolicy"], "slowstart": rows["pReduceSlowstart"],
                "racks": np.round(rows["pNumRacks"]), "cross_bw": rows["crossRackBw"],
                "oversub": rows["oversubscription"], "dep": dep,
            })
        return out
