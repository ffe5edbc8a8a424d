"""Stdlib-only Prometheus text-exposition checker.

Validates the output of ``GET /metrics`` without depending on a Prometheus
client library:

  * every sample line parses as ``name[{labels}] value``;
  * every sample belongs to a family announced by a ``# TYPE`` line, and the
    family's samples match its type (``counter``/``gauge`` are plain samples;
    ``histogram`` families expose ``_bucket``/``_sum``/``_count`` series);
  * histogram buckets are cumulative-monotone in ``le`` order, end with a
    ``+Inf`` bucket, and the ``+Inf`` count equals the ``_count`` sample;
  * counters are non-negative.

``--require NAME`` (repeatable) additionally asserts that a family is
present, pinning the families the observability layer promises.  The tests
call :func:`parse`, which checks a text and returns its sample values.

    curl -s localhost:8080/metrics | python tests/promcheck.py \
        --require masksearch_queries_total
"""

from __future__ import annotations

import argparse
import re
import sys

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>[^ ]+)$")
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"$')


def _family_of(name: str, typed: dict) -> str | None:
    """Map a sample name to its announced family (histograms expose
    ``<fam>_bucket``/``_sum``/``_count`` under family ``<fam>``)."""
    if name in typed:
        return name
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and name[: -len(suffix)] in typed:
            return name[: -len(suffix)]
    return None


def check(text: str, required=()) -> tuple[list[str], dict, dict]:
    """``(errors, {"name{labels}": value}, {family: type})`` of ``text``."""
    errors: list[str] = []
    samples: dict[str, float] = {}
    typed: dict[str, str] = {}
    helped: set[str] = set()
    # histogram buckets keyed by (family, non-le labels) -> [(le, count)]
    buckets: dict[tuple, list[tuple[float, float]]] = {}
    counts: dict[tuple, float] = {}
    sums: set[tuple] = set()

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            helped.add(line.split(" ", 3)[2])
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            if len(parts) != 4 or parts[3] not in ("counter", "gauge",
                                                   "histogram"):
                errors.append(f"line {lineno}: malformed TYPE line: {line!r}")
                continue
            if parts[2] in typed:
                errors.append(f"line {lineno}: duplicate TYPE for {parts[2]}")
            typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue

        m = _SAMPLE_RE.match(line)
        if not m:
            errors.append(f"line {lineno}: unparsable sample: {line!r}")
            continue
        name, raw_labels, raw_value = (m.group("name"), m.group("labels"),
                                       m.group("value"))
        try:
            value = float(raw_value)
        except ValueError:
            errors.append(f"line {lineno}: non-numeric value {raw_value!r}")
            continue
        samples[line.rsplit(" ", 1)[0]] = value
        labels = {}
        for pair in (raw_labels.split(",") if raw_labels else []):
            if not _LABEL_RE.match(pair):
                errors.append(f"line {lineno}: malformed label {pair!r}")
                break
            k, v = pair.split("=", 1)
            labels[k] = v.strip('"')

        fam = _family_of(name, typed)
        if fam is None:
            errors.append(f"line {lineno}: sample {name!r} has no TYPE line")
            continue
        ftype = typed[fam]
        key = (fam, tuple(sorted((k, v) for k, v in labels.items()
                                 if k != "le")))
        if ftype == "histogram":
            if name.endswith("_bucket"):
                if "le" not in labels:
                    errors.append(f"line {lineno}: bucket without le label")
                    continue
                le = (float("inf") if labels["le"] == "+Inf"
                      else float(labels["le"]))
                buckets.setdefault(key, []).append((le, value))
            elif name.endswith("_count"):
                counts[key] = value
            elif name.endswith("_sum"):
                sums.add(key)
            else:
                errors.append(f"line {lineno}: plain sample {name!r} in "
                              f"histogram family {fam!r}")
        else:
            if name != fam:
                errors.append(f"line {lineno}: suffixed sample {name!r} in "
                              f"{ftype} family {fam!r}")
            if ftype == "counter" and value < 0:
                errors.append(f"line {lineno}: negative counter {name!r}")

    for fam in typed:
        if fam not in helped:
            errors.append(f"family {fam!r}: TYPE without HELP")
    for key, series in buckets.items():
        fam = key[0]
        les = [le for le, _ in series]
        vals = [v for _, v in series]
        if les != sorted(les):
            errors.append(f"{fam}{dict(key[1])}: buckets out of le order")
        if vals != sorted(vals):
            errors.append(f"{fam}{dict(key[1])}: bucket counts not "
                          f"cumulative-monotone: {vals}")
        if not les or les[-1] != float("inf"):
            errors.append(f"{fam}{dict(key[1])}: missing +Inf bucket")
        elif key in counts and counts[key] != vals[-1]:
            errors.append(f"{fam}{dict(key[1])}: _count {counts[key]} != "
                          f"+Inf bucket {vals[-1]}")
        if key not in counts:
            errors.append(f"{fam}{dict(key[1])}: missing _count")
        if key not in sums:
            errors.append(f"{fam}{dict(key[1])}: missing _sum")

    for want in required:
        if want not in typed:
            errors.append(f"required family {want!r} absent from exposition")
    return errors, samples, typed


def parse(text: str, required=()) -> tuple[dict, dict]:
    """:func:`check` that raises AssertionError listing every fault."""
    errors, samples, typed = check(text, required)
    assert not errors, "\n".join(errors)
    return samples, typed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="-",
                    help="exposition file, or '-' for stdin (default)")
    ap.add_argument("--require", action="append", default=[],
                    help="family name that must be present (repeatable)")
    args = ap.parse_args()

    if args.path == "-":
        text = sys.stdin.read()
    else:
        with open(args.path) as f:
            text = f.read()

    errors, samples, typed = check(text, args.require)
    counts = f"{len(typed)} families, {len(samples)} samples"
    if errors:
        print(f"prometheus check FAILED ({counts}):", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print(f"prometheus check ok: {counts}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
