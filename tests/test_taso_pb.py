"""Binary TASO catalog reader (flexflow_tpu/pcg/taso_pb.py) and
default-on catalog resolution (rewrite.catalog_for_config).

The reference loads substitutions/graph_subst_3_v2.pb (proto2 wire
bytes) and ships a JSON twin via tools/protobuf_to_json; our .pb
reader must parse the binary form to rule-for-rule the same IR as the
JSON parse, and tools/pb_to_json.py must emit the converter's exact
schema.
"""
import json
import os
import subprocess
import sys

import pytest

from flexflow_tpu.pcg.taso import is_taso_rule_file, parse_rule_collection
from flexflow_tpu.pcg.taso_pb import looks_like_pb, pb_to_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the one in-checkout place a catalog is looked for
# (rewrite.default_substitution_catalog); the reference's 640-rule file
# is not committed, so these run only where someone has placed it there
PB = os.path.join(REPO, "substitutions", "graph_subst_3_v2.pb")
JS = os.path.join(REPO, "substitutions", "graph_subst_3_v2.json")

pytestmark = pytest.mark.skipif(
    not os.path.exists(PB), reason="no catalog under <repo>/substitutions/"
)


def test_pb_parses_identically_to_json():
    """Every one of the 640 rules decodes from wire bytes to exactly
    the rule the JSON twin yields (names, ops, params, mappings)."""
    a = parse_rule_collection(PB)
    b = parse_rule_collection(JS)
    assert len(a) == len(b) == 640
    assert a == b


def test_pb_dict_matches_converter_schema():
    """pb_to_dict emits the protobuf_to_json.cc structure verbatim —
    byte-equal JSON after normalization."""
    d = pb_to_dict(PB)
    with open(JS) as f:
        ref = json.load(f)
    assert d == ref


def test_pb_detection():
    assert looks_like_pb(PB) and not looks_like_pb(JS)
    assert is_taso_rule_file(PB) and is_taso_rule_file(JS)


def test_converter_cli_round_trip(tmp_path):
    out = tmp_path / "subst.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pb_to_json.py"),
         PB, str(out)],
        capture_output=True, text=True, check=True,
    )
    assert "Loaded 640 rules." in r.stdout
    with open(out) as f:
        assert json.load(f) == pb_to_dict(PB)


def test_default_catalog_resolution(monkeypatch):
    """Default-on: no --substitution-json resolves to a findable
    catalog; ""/"none" disables; env override wins."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.pcg.rewrite import catalog_for_config

    monkeypatch.delenv("FLEXFLOW_TPU_SUBSTITUTIONS", raising=False)
    assert catalog_for_config(FFConfig()) is not None
    assert catalog_for_config(FFConfig(substitution_json="none")) is None
    assert catalog_for_config(FFConfig(substitution_json="")) is None
    assert catalog_for_config(
        FFConfig(substitution_json=JS)) == JS
    monkeypatch.setenv("FLEXFLOW_TPU_SUBSTITUTIONS", "")
    assert catalog_for_config(FFConfig()) is None
    monkeypatch.setenv("FLEXFLOW_TPU_SUBSTITUTIONS", PB)
    assert catalog_for_config(FFConfig()) == PB


def test_strategy_replay_pins_catalog(monkeypatch):
    """A strategy whose trace references catalog rules records the
    catalog identity; replay must load byte-identical rules or fail
    loudly (match indices would silently select different subgraphs)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.pcg.rewrite import (
        catalog_fingerprint,
        rules_for_replay,
    )
    from flexflow_tpu.strategy import Strategy

    monkeypatch.delenv("FLEXFLOW_TPU_SUBSTITUTIONS", raising=False)
    fp = catalog_fingerprint(PB)
    s = Strategy(mesh_axes={"data": 2},
                 rewrites=[("taso_rule_0@2", 0)], catalog=fp)
    rules = rules_for_replay(FFConfig(), s)
    assert any(r.name.startswith("taso_rule_") for r in rules)

    bad = Strategy(mesh_axes={"data": 2}, rewrites=[("taso_rule_0@2", 0)],
                   catalog=dict(fp, sha256="0" * 64))
    with pytest.raises(ValueError, match="differs"):
        rules_for_replay(FFConfig(), bad)

    old = Strategy(mesh_axes={"data": 2}, rewrites=[("taso_rule_0@2", 0)],
                   catalog=dict(fp, engine=-1))
    with pytest.raises(ValueError, match="engine"):
        rules_for_replay(FFConfig(), old)

    # no catalog findable anywhere -> clear error, not silent mis-replay
    monkeypatch.setenv("FLEXFLOW_TPU_SUBSTITUTIONS", "")
    gone = Strategy(mesh_axes={"data": 2}, rewrites=[("taso_rule_0@2", 0)],
                    catalog=dict(fp, path="/nonexistent/catalog.pb"))
    with pytest.raises(ValueError, match="no catalog"):
        rules_for_replay(FFConfig(), gone)

    # traces without catalog rules replay exactly as before
    plain = Strategy(mesh_axes={"data": 2},
                     rewrites=[("fuse_linear_activation", 0)])
    assert rules_for_replay(FFConfig(substitution_json="none"), plain)


def test_default_catalog_loads_in_search_rule_list():
    """rules_for_config with the default config includes compiled
    catalog pattern rules (the flagship feature is live by default)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.pcg.rewrite import rules_for_config

    rules = rules_for_config(FFConfig())
    assert any(r.name.startswith("taso_rule_") for r in rules)
