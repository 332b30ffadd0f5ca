"""The program's spans as a tree: every record carries `parent`, the id of
the span open on its thread when it started, so a reader can take one
serving-loop iteration apart and give a layer its self time. Records of a
program that writes no `parent` (or no `serving.loop`) yield nothing, and
the readers built on this return None."""
import collections

LOOP = "serving.loop"


def end_us(span):
    return span["ts"] + span["dur"]


def iterations(spans):
    """One entry per `serving.loop` span, in time order: {name: [spans]} of
    the loop span and everything recorded under it, at any depth. The
    per-request copies of a decode step (no `batch`) are left out."""
    by_id = {s["id"]: s for s in spans}
    trees = {s["id"]: collections.defaultdict(list)
             for s in spans if s["name"] == LOOP}
    for s in spans:
        if s["name"] == "serving.decode" and "batch" not in s.get("attrs", {}):
            continue
        top = s
        while top is not None and top["name"] != LOOP:
            top = by_id.get(top.get("parent"))
        if top is not None:
            trees[top["id"]][s["name"]].append(s)
    return sorted(trees.values(), key=lambda t: t[LOOP][0]["ts"])


def one(tree, name):
    """The iteration's one span of that name, or None."""
    found = tree.get(name, [])
    return found[0] if len(found) == 1 else None


def self_us(span, spans):
    """A span's duration less the part of it its children cover, counted
    once where children overlap. A child that started before its parent is
    a record made after the fact (a request's wait in the queue, filed when
    it is admitted), not work done inside it, and is left out."""
    lo, hi = span["ts"], end_us(span)
    covered, at = 0, lo
    for b, e in sorted((c["ts"], end_us(c)) for c in spans
                       if c.get("parent") == span["id"] and c["ts"] >= lo):
        b, e = max(b, at), min(e, hi)
        if e > b:
            covered += e - b
            at = e
    return span["dur"] - covered
