"""JSON-Schema-constrained decoding (`json_schema` sampling param —
xgrammar / vLLM guided_json / OpenAI response_format=json_schema analog):
the schema-compiled NFA accepts exactly schema-valid compact JSON, engine
outputs parse AND validate, and the constraint composes with the rest of
the stack."""

import json

import jax
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.grammar import JsonSchemaGrammar
from rbg_tpu.engine.tokenizer import ByteTokenizer
from rbg_tpu.models import get_config, init_params


def _full(g, s: str) -> bool:
    st = g.initial()
    for b in s.encode():
        st = g.advance(st, b)
        if st is None:
            return False
    return g.is_complete(st)


SCHEMA = {"type": "object", "properties": {
    "name": {"type": "string", "minLength": 1},
    "age": {"type": "integer"},
    "tags": {"type": "array", "items": {"enum": ["a", "b"]}, "maxItems": 3},
    "score": {"type": "number"},
    "ok": {"type": "boolean"},
    "ref": {"type": "null"},
    "kind": {"const": "user"},
}}


def test_schema_grammar_accepts_only_valid_documents():
    g = JsonSchemaGrammar(SCHEMA)
    good = ('{"name":"bob","age":42,"tags":["a","b"],"score":-1.5e3,'
            '"ok":true,"ref":null,"kind":"user"}')
    assert _full(g, good)
    json.loads(good)  # and it IS JSON
    for bad in (
        '{"name":"bob"}',                      # missing properties
        good.replace('"user"', '"x"'),         # const violated
        good.replace("42", "4.2"),             # integer violated
        good.replace('"name"', '"nope"', 1),   # wrong key
        good.replace('["a","b"]', '["c"]'),    # enum violated
        good.replace('"bob"', '""'),           # minLength violated
        " " + good,                            # whitespace (compact only)
    ):
        assert not _full(g, bad), bad


def test_schema_grammar_strings_are_utf8_safe():
    g = JsonSchemaGrammar({"type": "string"})
    for s in ('"héllo"', '"a\\nb"', '"\\u00e9"', '"日本"', '"🙂"', '""'):
        assert _full(g, s), s
        json.loads(s)
    # Raw UTF-8 fragment bytes are never legal string content.
    st = g.initial()
    st = g.advance(st, ord('"'))
    assert g.advance(st, 0x80) is None
    # Unpaired surrogate lead byte patterns (0xED 0xA0..) are rejected.
    st2 = g.advance(st, 0xED)
    assert st2 is None or g.advance(st2, 0xA0) is None


def test_schema_grammar_features():
    g = JsonSchemaGrammar({"type": "string", "pattern": r"[A-Z]{2}\d{4}"})
    assert _full(g, '"AB1234"') and not _full(g, '"ab1234"')
    g = JsonSchemaGrammar({"anyOf": [{"type": "integer"}, {"type": "null"}]})
    assert _full(g, "7") and _full(g, "null") and not _full(g, '"7"')
    g = JsonSchemaGrammar({"type": "array", "items": {"type": "integer"},
                           "minItems": 2, "maxItems": 3})
    assert _full(g, "[1,2]") and _full(g, "[1,2,3]")
    assert not _full(g, "[1]") and not _full(g, "[1,2,3,4]")
    g = JsonSchemaGrammar({"type": "array", "items": {"type": "null"}})
    assert _full(g, "[]") and _full(g, "[null,null]")
    g = JsonSchemaGrammar({"type": ["integer", "null"]})
    assert _full(g, "3") and _full(g, "null")
    g = JsonSchemaGrammar({"type": "object", "properties": {}})
    assert _full(g, "{}")


@pytest.mark.parametrize("keyword,value", [
    ("minimum", 3), ("multipleOf", 2), ("format", "date"),
    ("uniqueItems", True)])
def test_schema_grammar_refuses_keywords_it_does_not_enforce(keyword, value):
    """Accepted keywords are a whitelist: a constraint let through
    unenforced would emit output the client's own schema rejects."""
    schema = {"type": "object",
              "properties": {"n": {"type": "integer", keyword: value}}}
    with pytest.raises(ValueError, match="unrecognized constraint keyword"):
        JsonSchemaGrammar(schema)
    JsonSchemaGrammar({"type": "object", "title": "annotations pass",
                       "properties": {"n": {"type": "integer"}}})


def test_schema_grammar_rejects_unsupported():
    for bad in ({"$ref": "#/x"}, {"allOf": []}, {"type": "frob"},
                {"enum": []}, {"enum": [{"x": 1}]},
                {"type": "array", "minItems": 3, "maxItems": 1},
                # Array without "items" means any-value members — silently
                # emitting array-of-strings would diverge from the
                # client's schema; must raise at admission.
                {"type": "array"},
                {"type": "array", "minItems": 1},
                "not a dict"):
        with pytest.raises(ValueError):
            JsonSchemaGrammar(bad)


# ---- engine integration ----


@pytest.fixture(scope="module")
def eng():
    cfg = get_config("tiny", vocab_size=512)
    params = init_params(cfg, jax.random.key(0))
    e = Engine(EngineConfig(model="tiny", vocab_size=512, page_size=8,
                            num_pages=128, max_seq_len=256,
                            use_pallas="never"), params=params)
    e.mcfg = cfg
    e.enable_json_grammar(ByteTokenizer())
    return e


def test_schema_outputs_validate(eng):
    tok = ByteTokenizer()
    schema = {"type": "object", "properties": {
        "id": {"type": "integer"},
        "state": {"enum": ["on", "off"]},
    }}
    completed = 0
    for seed in range(3):
        rid = eng.add_request(
            tok.encode("emit:"),
            SamplingParams(max_new_tokens=48, temperature=0.9, seed=seed,
                           json_schema=schema, stop_token=tok.eos_id))
        out = []
        while eng.has_work():
            for ev in eng.step():
                if ev.request_id == rid:
                    out.append(ev.token)
        assert out                              # something was produced
        done = out[-1] == tok.eos_id
        text = tok.decode([t for t in out if t != tok.eos_id])
        if done:
            completed += 1
            doc = json.loads(text)              # parses...
            assert set(doc) == {"id", "state"}  # ...and validates
            assert isinstance(doc["id"], int)
            assert doc["state"] in ("on", "off")
        else:
            # Budget-truncated (the schema admits unbounded integer
            # digits): the emitted prefix must still be schema-legal,
            # and the truncation must be the BUDGET's doing.
            assert len(out) == 48, text
            g = JsonSchemaGrammar(schema)
            s = g.initial()
            for b in text.encode():
                s = g.advance(s, b)
                assert s is not None, text
    # EOS must actually be reachable: with these fixed seeds the engine
    # is deterministic and most runs complete — zero completions would
    # mean EOS never became legal (e.g. a broken is_complete/table row).
    assert completed >= 1


def test_schema_admission_and_cache(eng):
    with pytest.raises(ValueError, match="unsupported keyword"):
        eng.add_request([1, 2], SamplingParams(max_new_tokens=4,
                                               json_schema={"$ref": "#/x"}))
    with pytest.raises(ValueError, match="mutually exclusive"):
        SamplingParams(json_mode=True, json_schema={"type": "null"}).validate()
    s = {"type": "object", "properties": {"a": {"type": "null"}}}
    g1 = eng._grammar_for(SamplingParams(json_schema=s))
    g2 = eng._grammar_for(SamplingParams(json_schema=dict(s)))
    assert g1 is g2                         # keyed by canonical dump
    assert g1.trie is eng.grammar.trie      # shared tokenizer trie


def test_schema_malformed_shapes_are_value_errors():
    """TypeError must never escape compilation — the server maps only
    ValueError to a clean 'bad sampling params' reply."""
    for bad in ({"anyOf": []}, {"oneOf": "x"},
                {"type": "object", "properties": {"a": True}},
                {"type": "array", "items": None}):
        with pytest.raises(ValueError):
            JsonSchemaGrammar(bad)


def test_empty_schema_means_any_json(eng):
    g = eng._grammar_for(SamplingParams(json_schema={}))
    assert g is eng.grammar          # the generic JSON grammar
    # And from_wire must not drop it.
    sp = SamplingParams.from_wire({"json_schema": {}})
    assert sp.json_schema == {}


def test_empty_regex_means_empty_output_only():
    from rbg_tpu.engine.grammar import RegexGrammar
    g = RegexGrammar("")
    assert g.is_complete(g.initial())
    assert g.advance(g.initial(), ord("a")) is None
    sp = SamplingParams.from_wire({"regex": ""})
    assert sp.regex == ""


def test_semantic_regex_escapes_raise():
    from rbg_tpu.engine.grammar import RegexGrammar
    for pat in (r"\bfoo\b", r"\Astart", r"end\Z", r"\Bx"):
        with pytest.raises(ValueError, match="escape"):
            RegexGrammar(pat)
    # Escaped punctuation stays literal.
    g = RegexGrammar(r"\.\+")
    st = g.initial()
    for b in b".+":
        st = g.advance(st, b)
    assert g.is_complete(st)


def test_schema_cache_respects_property_order(eng):
    a_first = {"type": "object", "properties": {"a": {"type": "null"},
                                                "b": {"type": "null"}}}
    b_first = {"type": "object", "properties": {"b": {"type": "null"},
                                                "a": {"type": "null"}}}
    ga = eng._grammar_for(SamplingParams(json_schema=a_first))
    gb = eng._grammar_for(SamplingParams(json_schema=b_first))
    assert ga is not gb              # order-sensitive emission
    assert _full(ga.grammar, '{"a":null,"b":null}')
    assert _full(gb.grammar, '{"b":null,"a":null}')
    assert not _full(ga.grammar, '{"b":null,"a":null}')


def test_http_edge_maps_schema_fields():
    from rbg_tpu.engine.http_frontend import Handler

    f = Handler._sampling_fields
    s = {"type": "object", "properties": {"a": {"type": "null"}}}
    assert f({"guided_json": s})["json_schema"] == s
    assert f({"response_format": {"type": "json_schema",
                                  "json_schema": {"schema": s}}}
             )["json_schema"] == s
    assert f({"guided_regex": r"\d+"})["regex"] == r"\d+"
    with pytest.raises(ValueError):
        f({"response_format": {"type": "json_schema"}})
    with pytest.raises(ValueError):
        f({"guided_json": "not a schema"})


@pytest.mark.e2e
@pytest.mark.slow
def test_json_schema_over_wire():
    """guided_json through a real server subprocess: generate_text with a
    json_schema constraint returns text that parses AND validates."""
    from conftest import SpawnedEngineServer
    from rbg_tpu.engine.protocol import request_once

    with SpawnedEngineServer(
            "--model", "tiny", "--vocab-size", "512", "--page-size", "8",
            "--num-pages", "128", "--max-seq-len", "256",
            "--use-pallas", "never") as srv:
        schema = {"type": "object", "properties": {
            "n": {"type": "integer"},
            "tag": {"enum": ["x", "y"]}}}
        r, _, _ = request_once(
            srv.addr,
            {"op": "generate_text", "text": "emit:", "max_new_tokens": 40,
             "temperature": 0.8, "seed": 2, "json_schema": schema},
            timeout=180)
        assert "error" not in r, r
        assert r["text"]                       # something was produced
        g = JsonSchemaGrammar(schema)
        s = g.initial()
        for b in r["text"].encode():
            s = g.advance(s, b)
            assert s is not None, r["text"]     # schema-legal prefix
        if g.is_complete(s):
            doc = json.loads(r["text"])
            assert set(doc) == {"n", "tag"} and doc["tag"] in ("x", "y")
            assert isinstance(doc["n"], int)
        else:
            # Incomplete is acceptable ONLY as budget truncation (byte
            # tokenizer: one token per byte, EOS filtered server-side) —
            # an engine that stalls or never legalizes EOS fails here.
            assert len(r["text"].encode()) == 40, r["text"]
        # A malformed schema is a clean per-request error, not a dead wire.
        r2, _, _ = request_once(
            srv.addr,
            {"op": "generate_text", "text": "emit:", "max_new_tokens": 8,
             "json_schema": {"$ref": "#/x"}}, timeout=60)
        assert "error" in r2 and "unsupported keyword" in r2["error"]
