"""Share of the window's wall time the trainer's host thread spent
fetching batches, placing them and reporting, from the program's own
`Timeline` (`trainer/_timeline.py`: `data_wait`, `h2d_put`, `report`),
as the trainer reports it under the `profiling` group."""
LAYER = "trainer"
UNIT = "%"
SOURCE = "program_span"


def read(run):
    r = run.records
    if r["kind"] != "train" or not r["timelines"]:
        return None
    wall = sum(t["window_s"] for t in r["timelines"])
    host = sum(
        t["window_s"] * (t.get("data_wait_frac", 0.0)
                         + t.get("h2d_put_frac", 0.0)
                         + t.get("report_frac", 0.0))
        for t in r["timelines"])
    return 100.0 * host / wall if wall > 0 else None
