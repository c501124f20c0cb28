"""Host time between consecutive ``round`` spans of the trainer's own
tracer (``RoundRunner``'s span around each round, which ends on
``block_until_ready``) over the traced window, summed and divided by the
window's rounds: progress, diagnostics and the next round's staging."""
import json


def read(art):
    if art.get("kind") != "train":
        return None
    events = json.loads(open(art["spans"]).read())["traceEvents"]
    rounds = sorted((e for e in events if e.get("name") == "round"),
                    key=lambda e: e["ts"])[-art["rounds"]:]
    if len(rounds) < 2:
        return None
    gaps = [b["ts"] - (a["ts"] + a["dur"]) for a, b in zip(rounds, rounds[1:])]
    return sum(gaps) / 1e3 / len(rounds)
