"""Median duration of the program's ``train.step`` span over the window's
steps: the host's time in one ``ElasticTrainer.train_step`` call, with
every hook round the dispatch (compile watch, tracer) and none of the
device's time. From the tracer's ring, host clock."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    steps = program_spans.window_steps(ctx, spans)
    if not steps:
        return None
    return 1e3 * stats.median([program_spans.seconds(sp) for sp in steps])
