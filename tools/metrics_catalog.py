"""Metrics catalog extractor: the checked-in contract for the scrape surface.

Constructs one PrimaryNode and one WorkerNode from the deterministic
CommitteeFixture WITHOUT spawning them — every metric in the repo is
registered at assembly time (constructors create channels, role metrics
objects, and the backpressure gauge), so construction alone materialises the
full per-role registry. The extracted {name, type, labels, help} rows are
diffed against tools/metrics_catalog.json by tests/test_telemetry.py: adding,
renaming, or dropping a metric without updating the catalog fails the gate,
which is how dashboards and scrapers learn about surface changes in review
instead of in production.

Regenerate after an intentional change:

    JAX_PLATFORMS=cpu python -m tools.metrics_catalog --write
"""

from __future__ import annotations

import json
import os

CATALOG_PATH = os.path.join(os.path.dirname(__file__), "metrics_catalog.json")

# Span stages are catalog rows too: the flight-recorder stage vocabulary
# is a scrape-surface contract exactly like metric names — waterfall
# stitching and the stage-percentile tables key on these strings, so a
# renamed or drive-by stage must show up in review as catalog drift. Rows are
# `span:<stage>` with type "span_stage"; `roles` names the recording
# component.
SPAN_STAGES: tuple[tuple[str, str, str], ...] = (
    ("seal", "worker", "batch sealed by a worker's BatchMaker"),
    ("propose", "primary", "header proposed for the batch digests"),
    ("certify", "primary", "votes aggregated into a certificate"),
    ("commit", "consensus", "certificate committed by the commit rule"),
    ("execute", "executor", "committed payload applied to execution state"),
    ("verify_stage", "primary", "a header, vote or certificate inside the "
     "verifier stage: taken in -> verdicts in -> forwarded to the core"),
)


def span_stage_rows() -> list[dict]:
    return [
        {
            "name": f"span:{stage}",
            "type": "span_stage",
            "labels": [],
            "help": help_,
            "roles": [role],
        }
        for stage, role, help_ in SPAN_STAGES
    ]


def extract_catalog() -> list[dict]:
    """Build both role registries and return sorted catalog rows."""
    # cpu + full cert format keeps assembly free of the async verifier pool
    # (and of any accelerator imports): registration is identical across
    # backends — backends change metric VALUES, never the surface.
    from narwhal_tpu.config import Parameters
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import PrimaryNode, WorkerNode
    from narwhal_tpu.stores import NodeStorage

    fixture = CommitteeFixture(size=4, workers=1, seed=0)
    parameters = Parameters()
    parameters.cert_format = "full"
    auth = fixture.authority(0)

    primary = PrimaryNode(
        auth.keypair,
        fixture.committee,
        fixture.worker_cache,
        parameters,
        NodeStorage(None),
        network_keypair=auth.network_keypair,
    )
    worker = WorkerNode(
        auth.public,
        0,
        fixture.committee,
        fixture.worker_cache,
        parameters,
        NodeStorage(None),
        network_keypair=auth.worker_keypairs[0],
    )

    rows: dict[str, dict] = {}
    for role, registry in (("primary", primary.registry), ("worker", worker.registry)):
        for name, metric in registry._metrics.items():
            row = rows.get(name)
            if row is None:
                rows[name] = {
                    "name": name,
                    "type": metric.kind,
                    "labels": list(metric.label_names),
                    "help": metric.help,
                    "roles": [role],
                }
            elif role not in row["roles"]:
                row["roles"].append(role)
    primary.storage.close()
    worker.storage.close()
    return sorted(
        list(rows.values()) + span_stage_rows(), key=lambda r: r["name"]
    )


def load_catalog() -> list[dict]:
    with open(CATALOG_PATH) as f:
        return json.load(f)


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--write", action="store_true",
        help="regenerate tools/metrics_catalog.json from the live registries",
    )
    args = parser.parse_args()
    catalog = extract_catalog()
    if args.write:
        with open(CATALOG_PATH, "w") as f:
            json.dump(catalog, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(catalog)} metrics to {CATALOG_PATH}")
        return 0
    checked = {r["name"]: r for r in load_catalog()}
    live = {r["name"]: r for r in catalog}
    missing = sorted(set(live) - set(checked))
    stale = sorted(set(checked) - set(live))
    changed = sorted(
        n for n in set(live) & set(checked) if live[n] != checked[n]
    )
    for kind, names in (("undocumented", missing), ("stale", stale), ("changed", changed)):
        for n in names:
            print(f"{kind}: {n}")
    if missing or stale or changed:
        print("catalog drift — rerun with --write and review the diff")
        return 1
    print(f"catalog clean ({len(catalog)} metrics)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
