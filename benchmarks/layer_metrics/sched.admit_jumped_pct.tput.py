"""Share of the window's admissions that went ahead of a request queued
before them: ``generation.admit`` events whose ``jumped`` (the number of
earlier-queued requests still waiting when the request got its slot) is
over 0. 0 where every pass admits in arrival order; how often the
admission pass chose its batch by rung where more waited than it could
take. A program whose events carry no ``jumped`` (the commit before)
leaves nothing to read."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    admits = [a for a in program_events.instants(obs, "generation.admit")
              if "jumped" in a]
    if not admits:
        return None
    return 100.0 * sum(a["jumped"] > 0 for a in admits) / len(admits)
