"""Framework self-tests for the spec-oracle compiler (reference analogue:
tests/infra/test_md_to_spec.py — the reference unit-tests its markdown->
spec pipeline as a first-class tier; SURVEY §4 tier 1)."""

import os

import pytest

from eth_consensus_specs_tpu.specc import compiler as c
from eth_consensus_specs_tpu.specc.parser import parse_doc

DOC = '''# Sample spec

## Custom types

| Name | SSZ equivalent | Description |
| - | - | - |
| `Widget` | `uint64` | a widget |

## Constants

| Name | Value |
| - | - |
| `WIDGET_LIMIT` | `uint64(2**4)` (= 16) |

## Containers

```python
class Box(Container):
    w: Widget
```

## Helpers

```python
def double_widget(w: Widget) -> Widget:
    return Widget(w * 2)
```

```python
def get_payload(self: ExecutionEngine, payload_id) -> bool:
    return True
```
'''


@pytest.fixture()
def doc(tmp_path):
    p = tmp_path / "sample.md"
    p.write_text(DOC)
    return parse_doc(str(p))


def test_parser_classifies_functions(doc):
    assert "double_widget" in doc.functions
    assert "double_widget" not in doc.protocol_methods


def test_parser_classifies_protocol_methods(doc):
    # first parameter `self` routes to the protocol bucket
    assert "get_payload" in doc.protocol_methods
    assert "get_payload" not in doc.functions


def test_parser_classifies_classes(doc):
    assert "Box" in doc.classes
    assert "class Box(Container):" in doc.classes["Box"]


def test_parser_table_items_in_document_order(doc):
    kinds = [k for k, _, _ in doc.table_items]
    names = [n for _, n, _ in doc.table_items]
    assert names == ["Widget", "WIDGET_LIMIT"]
    assert kinds == ["ctype", "const"]


def test_parser_constant_value_expression(doc):
    (_, _, expr) = [t for t in doc.table_items if t[1] == "WIDGET_LIMIT"][0]
    assert expr == "uint64(2**4)"


def test_parse_doc_from_text_matches_file(tmp_path):
    p = tmp_path / "b.md"
    p.write_text(DOC)
    via_file = parse_doc(str(p))
    via_text = parse_doc(str(p), text=DOC)
    assert via_file.functions.keys() == via_text.functions.keys()
    assert via_file.table_items == via_text.table_items


# == compiled-oracle structure ==============================================


def test_compile_fork_exposes_spec_surface(reference_tree):
    m = c.compile_fork("phase0", "minimal")
    assert callable(m.state_transition)
    assert callable(m.process_epoch)
    assert m.SLOTS_PER_EPOCH == 8  # minimal preset substitution


def test_compile_fork_preset_substitution_differs(reference_tree):
    minimal = c.compile_fork("phase0", "minimal")
    mainnet = c.compile_fork("phase0", "mainnet")
    assert int(minimal.SLOTS_PER_EPOCH) != int(mainnet.SLOTS_PER_EPOCH)


def test_compile_fork_lineage_override(reference_tree):
    """A later fork's markdown redefinition replaces the ancestor's."""
    p0 = c.compile_fork("phase0", "minimal")
    altair = c.compile_fork("altair", "minimal")
    # altair modifies process_epoch (adds inactivity/participation steps)
    assert p0.process_epoch.__code__.co_code != altair.process_epoch.__code__.co_code


def test_compile_fork_ancestor_modules_linked(reference_tree):
    electra = c.compile_fork("electra", "minimal")
    # upgrade functions address ancestors as modules
    assert hasattr(electra, "deneb")
    assert callable(electra.deneb.get_current_epoch)


def test_compile_fork_builder_classes_injected(reference_tree):
    deneb = c.compile_fork("deneb", "minimal")
    from eth_consensus_specs_tpu.utils.bls import Scalar

    assert issubclass(deneb.BLSFieldElement, Scalar)
    poly = deneb.Polynomial()
    assert len(poly) == int(deneb.FIELD_ELEMENTS_PER_BLOB)


def test_compile_fork_rejects_unknown_fork():
    with pytest.raises(ValueError):
        c.compile_fork("notafork", "minimal")


def test_fork_choice_namespace_layers_on_top(reference_tree):
    plain = c.compile_fork("phase0", "minimal")
    fc = c.compile_fork("phase0", "minimal", None, True)
    assert not hasattr(plain, "on_block")
    assert hasattr(fc, "on_block") and hasattr(fc, "Store")
    # beacon-chain surface identical in both
    assert plain.SLOTS_PER_EPOCH == fc.SLOTS_PER_EPOCH


def test_zero_skip_reports_across_lineage(reference_tree):
    for fork in c.CHAIN:
        rep = c.compile_fork(fork, "minimal").__specc_report__
        assert not rep.skipped_constants, (fork, rep.skipped_constants)
        assert not rep.skipped_types, (fork, rep.skipped_types)


# == content pinning ========================================================


def test_pins_cover_every_compiled_doc():
    pins = c._load_pins()
    for fork in c.CHAIN:
        for name in c.DOC_SETS[fork] + c.FC_DOCS.get(fork, []):
            rel = os.path.join("specs", fork, name)
            full = os.path.join(c.REFERENCE_SPECS, rel)
            if os.path.exists(full):
                assert rel in pins, f"unpinned compiled doc {rel}"


def test_read_pinned_rejects_tampered_content(reference_tree, tmp_path, monkeypatch):
    target = os.path.join(c.REFERENCE_SPECS, "specs", "phase0", "beacon-chain.md")
    tampered = tmp_path / "beacon-chain.md"
    tampered.write_text(open(target).read() + "\n<!-- tampered -->\n")

    real_relpath = os.path.relpath

    def fake_relpath(path, start):
        if str(tampered) in str(path):
            return os.path.join("specs", "phase0", "beacon-chain.md")
        return real_relpath(path, start)

    monkeypatch.setattr(os.path, "relpath", fake_relpath)
    with pytest.raises(RuntimeError, match="content hash"):
        c._read_pinned(str(tampered))


def test_read_pinned_rejects_unpinned_file(tmp_path):
    stray = tmp_path / "stray.md"
    stray.write_text("# not a spec\n")
    with pytest.raises(RuntimeError, match="not in pins.json"):
        c._read_pinned(str(stray))
