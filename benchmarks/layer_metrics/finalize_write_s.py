"""Job wall minus every phase ``pipeline_stats`` accounts for: the final
merge, the output sort and the writing of ``mr-out-*``, for which the program
has no span yet.  Serial host work that more chips do not shorten."""

from layer_metrics._common import median_of, pipeline_stats

_PHASES = ("batch_s", "upload_s", "kernel_s", "pull_s", "merge_s",
           "replay_s")


def read(obs):
    return median_of([p["wall_s"] - sum(p.get(k, 0.0) for k in _PHASES)
                      for p in pipeline_stats(obs)])
