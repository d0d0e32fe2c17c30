from repro.obs.tracker import (  # noqa: F401
    NOOP, NULL_SPAN, SCHEMA_VERSION, CompositeTracker, InMemoryTracker,
    JsonlTracker, NoopTracker, Tracker, clock_ns, read_jsonl, replay)
