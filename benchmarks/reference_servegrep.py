"""The plain reference for one wave of grep jobs served by ``mrserve``: what
each job's result must hold, as if its tenant ran alone.

A wave is a list of jobs, dealt from the traffic mix's ``tenants`` over the
corpus's files in order: tenant after tenant, each tenant's jobs in the
order the mix gives them, each job the next ``size`` files, every file in
exactly one job.  A job's answer is what ``reference_grepstats.lines`` gives
over that job's own files in that order, for its tenant's literal; each of
its lines is prefixed ``"<tenant>/<k> "``, where ``k`` numbers the job
within its tenant from 0, so that the lines of all jobs of a wave stand
side by side in one sorted list.

Straightforward Python; imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List

import reference_grepstats


def deal(tenants: List[dict], n_files: int) -> List[dict]:
    """The wave's jobs: ``{"tenant", "k", "pattern", "files"}`` with
    ``files`` the indexes into the corpus's file list.  ``tenants`` is
    the mix's list of ``{"tenant", "pattern", "jobs": [sizes in files]}``.
    The sizes have to add up to the corpus: a mix that leaves a file over,
    or asks for one more, is a broken mix."""
    jobs, at = [], 0
    for t in tenants:
        for k, size in enumerate(t["jobs"]):
            jobs.append({"tenant": t["tenant"], "k": k,
                         "pattern": t["pattern"],
                         "files": list(range(at, at + int(size)))})
            at += int(size)
    if at != n_files:
        raise ValueError(f"the mix deals {at} files, the corpus has "
                         f"{n_files}")
    return jobs


def job_lines(tenant: str, k: int, stats_lines: List[str]) -> List[str]:
    """One job's lines in the wave's list."""
    return [f"{tenant}/{k} {line}" for line in stats_lines]


def lines(paths: List[str], params: Dict[str, object]) -> List[str]:
    out: List[str] = []
    for job in deal(params["tenants"], len(paths)):
        got = reference_grepstats.lines(
            [paths[i] for i in job["files"]],
            {"pattern": job["pattern"], "bins": params["bins"],
             "topk": params["topk"], "passes": params.get("passes", 1)})
        out += job_lines(job["tenant"], job["k"], got)
    return sorted(out)
