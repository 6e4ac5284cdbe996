"""Documentation gates (VERDICT r4 next #5): the expconf field reference
is GENERATED from the validator module's registry and fails here when it
drifts; the guides must exist, cross-link to real files, and name only
real CLI verbs and searcher/axis values."""
import os
import re

from determined_tpu.master import expconf

DOCS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs"
)


def _read(name):
    return open(os.path.join(DOCS, name)).read()


class TestExpconfReference:
    def test_reference_is_in_sync(self):
        """docs/expconf-reference.md must byte-match the generator —
        regenerate with `python -m determined_tpu.master.expconf >
        docs/expconf-reference.md` after editing FIELDS."""
        assert _read("expconf-reference.md") == expconf.generate_reference()

    def test_registry_covers_validator_value_sets(self):
        """Every value set the validator enforces appears in the
        generated reference — extend one without the other and this
        fails."""
        ref = expconf.generate_reference()
        for name in expconf.KNOWN_SEARCHERS:
            assert f"`{name}`" in ref, name
        for typ in expconf.KNOWN_STORAGE:
            assert f"`{typ}`" in ref, typ
        for axis in expconf.MESH_AXES:
            assert f"`{axis}`" in ref, axis

    def test_registry_covers_validator_checked_paths(self):
        """Every config path validate() produces errors about has a
        registry row (prefix match — hyperparameters document as a
        pattern)."""
        paths = {p for p, _, _, _ in expconf.FIELDS}
        for checked in (
            "entrypoint", "searcher.name", "searcher.max_trials",
            "searcher.max_length", "searcher.mesh_candidates",
            "resources.slots_per_trial", "resources.priority",
            "resources.weight", "resources.max_slots", "mesh",
            "checkpoint_storage.type", "checkpoint_storage.host_path",
            "checkpoint_storage.bucket", "checkpoint_storage.container",
            "checkpoint_storage.save_experiment_best",
            "checkpoint_storage.save_trial_best",
            "checkpoint_storage.save_trial_latest",
            "min_validation_period", "min_checkpoint_period",
            "scheduling_unit", "max_restarts", "hyperparameters",
        ):
            assert any(
                p == checked or p.startswith(checked + ".")
                or p.startswith(checked + "<") or checked in p
                for p in paths
            ), checked

    def test_builtin_defaults_documented(self):
        """Every builtin default value appears in its field's Default
        column."""
        by_path = {p: d for p, _, d, _ in expconf.FIELDS}
        assert by_path["searcher.name"] == "single"
        assert by_path["resources.slots_per_trial"] == "1"
        assert by_path["resources.priority"] == "50"
        assert by_path["max_restarts"] == "5"
        assert by_path["scheduling_unit"] == "100"
        # and the registry's claims match BUILTIN_DEFAULTS itself
        d = expconf.BUILTIN_DEFAULTS
        assert d["searcher"]["name"] == "single"
        assert d["resources"] == {"slots_per_trial": 1, "priority": 50}
        assert d["max_restarts"] == 5 and d["scheduling_unit"] == 100


class TestGuides:
    REQUIRED = {
        "quickstart.md": ("deploy local up", "experiment create",
                          "checkpoint download", "examples/mnist.json"),
        "hp-search.md": ("adaptive_asha", "autotune", "mesh_candidates",
                         "max_trials", "SearchRunner"),
        "dtrain.md": ("fsdp", "tensor", "pipeline", "context", "expert",
                      "1f1b", "zigzag", "ulysses", "dryrun_multichip",
                      "multislice"),
        "deploy.md": ("deploy local", "deploy gcp", "deploy k8s",
                      "provisioner", "spot"),
        "operations.md": ("drain", "DTPU_PG_DSN", "tunnel",
                          # time-series plane (PR 9)
                          "metrics/query", "burn_rate", "ALERT",
                          "scrape_interval_s", "master.scrape",
                          # trace plane (PR 10)
                          "Trace plane", "traces/ingest",
                          "min_duration_ms", "client.trace_ship",
                          "master.trace_ingest", "DTPU_TRACE_SAMPLE",
                          "dtpu_lifecycle_segment_seconds",
                          "max_spans_per_trace", "EXEMPLAR",
                          "traces show",
                          # profiling plane (PR 12)
                          "Profiling plane", "profiles/ingest",
                          "client.profile_ship", "master.profile_ingest",
                          "stack-table-full", "profiles flame",
                          "profiles capture", "dtpu_step_flops",
                          "sample_hz",
                          # log plane (PR 13)
                          "Log plane", "logs/ingest", "logs query",
                          "logs tail", "client.log_ship",
                          "master.log_ingest", "ship_level",
                          "max_lines_per_target", "log_error_burst",
                          "dtpu_log_lines_total",
                          "dtpu_task_log_rows_trimmed_total",
                          # load harness + overload control (PR 15)
                          "loadtest run", "Retry-After",
                          "dtpu_ingest_shed_total", "master.overload",
                          "client.ingest_backoff", "max_inflight",
                          "retry_after_s", "coordinated omission",
                          "dtpu_master_tick_duration_seconds"),
        "expconf-reference.md": ("slots_per_trial", "max_slots",
                                 "checkpoint_storage",
                                 "profiling.sample_hz"),
    }

    def test_guides_exist_with_key_content(self):
        for name, needles in self.REQUIRED.items():
            text = _read(name)
            for needle in needles:
                assert needle in text, (name, needle)

    def test_cross_links_resolve(self):
        """Every relative .md/.json link or reference in docs/ points at a
        real file."""
        for name in os.listdir(DOCS):
            if not name.endswith(".md"):
                continue
            text = _read(name)
            repo = os.path.dirname(DOCS)
            for m in re.finditer(r"\(([\w\-./]+\.(?:md|json))\)", text):
                target = m.group(1)
                # links resolve relative to docs/, or to the repo root
                # (SURVEY.md, PERF.md live there)
                assert (
                    os.path.exists(os.path.join(DOCS, target))
                    or os.path.exists(os.path.join(repo, target))
                ), (name, target)
            for m in re.finditer(r"examples/[\w\-.]+\.(?:json|py)", text):
                assert os.path.exists(
                    os.path.join(os.path.dirname(DOCS), m.group(0))
                ), (name, m.group(0))

    def test_quickstart_verbs_are_real(self):
        """Every `dtpu <noun> <verb>` the quickstart shows parses in the
        actual CLI."""
        from determined_tpu.cli.cli import build_parser

        parser = build_parser()
        text = _read("quickstart.md")
        cmds = re.findall(r"^dtpu ([a-z]+) ([a-z][a-z\-]*)", text, re.M)
        assert cmds, "quickstart shows no commands?"
        # parse "--help"-less: resolve the subparser actions by name
        nouns = {
            a.dest: a for a in parser._subparsers._group_actions
        }["noun"].choices
        for noun, verb in cmds:
            assert noun in nouns, noun
            sub = nouns[noun]
            verbs = [
                c for act in (sub._subparsers._group_actions if
                              sub._subparsers else [])
                for c in act.choices
            ]
            if verbs:  # nouns without verbs (e.g. `dtpu tunnel`) skip
                assert verb in verbs, (noun, verb)
