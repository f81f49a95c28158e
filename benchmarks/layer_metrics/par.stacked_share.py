"""Of the tasks the device batcher answered in the window, the share that
rode a launch of two or more: 100 x (growth of `dgraph_batch_tasks_total`
- growth of `dgraph_batch_occupancy_bucket{le="1"}`) / growth of the
former. The occupancy histogram's first bucket counts the launches that
took one task alone, each of which answered one task. 0 when no task
reached the batcher. A program without the series: None."""

TASKS = "dgraph_batch_tasks_total"
ALONE = 'dgraph_batch_occupancy_bucket{le="1"}'


def read(run):
    if TASKS not in run.after["prom"] or ALONE not in run.after["prom"]:
        return None
    tasks = run.grown(TASKS)
    return 100.0 * (tasks - run.grown(ALONE)) / tasks if tasks else 0.0
