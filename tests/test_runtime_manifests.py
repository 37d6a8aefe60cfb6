"""Manifest parsing, validation, normalization, metadata store."""

import pytest

from kukeon_tpu.runtime import consts, model
from kukeon_tpu.runtime.api import types as t
from kukeon_tpu.runtime.api.wire import from_wire, to_wire
from kukeon_tpu.runtime.apply import parser, scheme
from kukeon_tpu.runtime.errors import InvalidArgument
from kukeon_tpu.runtime.metadata import MetadataStore

CELL_YAML = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata:
  name: agent-1
  space: proj
spec:
  autoDelete: true
  containers:
    - name: shell
      command: ["/bin/sh", "-c", "sleep 5"]
      env:
        - {name: FOO, value: bar}
      restartPolicy: {policy: on-failure, backoffSeconds: 2.0, maxRetries: 3}
      attachable: true
      resources: {tpuChips: 2}
---
apiVersion: kukeon.io/v1beta1
kind: Realm
metadata:
  name: prod
"""


def test_parse_multi_doc():
    docs = parser.parse_documents(CELL_YAML)
    assert [d.kind for d in docs] == ["Cell", "Realm"]
    cell = docs[0]
    assert cell.metadata.name == "agent-1"
    assert cell.spec.auto_delete is True
    c = cell.spec.containers[0]
    assert c.command == ["/bin/sh", "-c", "sleep 5"]
    assert c.restart_policy.policy == "on-failure"
    assert c.restart_policy.max_retries == 3
    assert c.resources.tpu_chips == 2
    assert c.attachable


def test_parse_rejects_unknown_field():
    bad = CELL_YAML.replace("autoDelete", "autoDeleteTypo")
    with pytest.raises(InvalidArgument, match="autoDeleteTypo"):
        parser.parse_documents(bad)


def test_parse_rejects_bad_kind_and_names():
    with pytest.raises(InvalidArgument, match="unknown kind"):
        parser.parse_documents("apiVersion: kukeon.io/v1beta1\nkind: Nope\nmetadata: {name: x}")
    with pytest.raises(InvalidArgument, match="invalid"):
        parser.parse_documents(
            "apiVersion: kukeon.io/v1beta1\nkind: Realm\nmetadata: {name: Bad_Name}"
        )


def test_parse_model_cell():
    docs = parser.parse_documents("""
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: llm}
spec:
  model: {model: llama3-8b, chips: 8, port: 9000, numSlots: 16}
""")
    assert docs[0].spec.model.chips == 8
    assert docs[0].spec.model.num_slots == 16


def test_scope_rules():
    with pytest.raises(InvalidArgument, match="not allowed"):
        parser.parse_documents(
            "apiVersion: kukeon.io/v1beta1\nkind: Realm\nmetadata: {name: r, space: s}"
        )
    with pytest.raises(InvalidArgument, match="stack scope requires space"):
        parser.parse_documents("""
apiVersion: kukeon.io/v1beta1
kind: Secret
metadata: {name: s, stack: st}
spec: {data: {K: v}}
""")


def test_normalize_defaults_scope():
    docs = parser.parse_documents(CELL_YAML)
    cell = scheme.normalize(docs[0])
    assert cell.metadata.realm == consts.DEFAULT_REALM
    assert cell.metadata.space == "proj"
    assert cell.metadata.stack == consts.DEFAULT_STACK


def test_sort_documents_dependency_order():
    blob = """
apiVersion: kukeon.io/v1beta1
kind: Cell
metadata: {name: c}
spec: {containers: [{name: x, command: [sh]}]}
---
apiVersion: kukeon.io/v1beta1
kind: Realm
metadata: {name: r}
---
apiVersion: kukeon.io/v1beta1
kind: Secret
metadata: {name: s}
spec: {data: {K: v}}
"""
    docs = parser.sort_documents(parser.parse_documents(blob))
    assert [d.kind for d in docs] == ["Realm", "Secret", "Cell"]
    rev = parser.sort_documents(docs, reverse=True)
    assert [d.kind for d in rev] == ["Cell", "Secret", "Realm"]


def test_wire_roundtrip_cell_record():
    docs = parser.parse_documents(CELL_YAML)
    rec = model.cell_record_from_doc(scheme.normalize(docs[0]))
    d = rec.to_json()
    rec2 = model.CellRecord.from_json(d)
    assert rec2.name == rec.name
    assert rec2.spec.containers[0].restart_policy.backoff_seconds == 2.0
    assert rec2.spec.containers[0].resources.tpu_chips == 2


def test_metadata_store(tmp_path):
    store = MetadataStore(str(tmp_path))
    store.write_json({"a": 1}, "realms", "default", "realm.json")
    assert store.read_json("realms", "default", "realm.json") == {"a": 1}
    assert store.list_dirs("realms") == ["default"]
    with store.lock("realms", "default"):
        store.write_json({"a": 2}, "realms", "default", "realm.json")
    assert store.read_json("realms", "default", "realm.json")["a"] == 2
    assert store.delete("realms", "default", "realm.json")
    assert not store.delete("realms", "default", "realm.json")


def test_serving_cell_stop_strings():
    """`stop` strings cut generation (and text) at the first match in both
    modes; `stopTokens` stop token-exactly."""
    import numpy as np

    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None)
    base = cell.generate({"prompt": "hello", "maxNewTokens": 6})
    assert base["numTokens"] == 6

    # Token-level stop: replay greedy and stop at the 2nd generated token.
    stop_tok = base["tokens"][1]
    out = cell.generate({"prompt": "hello", "maxNewTokens": 6,
                         "stopTokens": [int(stop_tok)]})
    assert out["tokens"] == base["tokens"][:2]

    # String-level stop: pick a substring of the full decode that first
    # appears at a known offset; text must be cut before it.
    full = base["text"]
    if len(full) >= 2:
        stop_s = full[1:2]
        out = cell.generate({"prompt": "hello", "maxNewTokens": 6,
                             "stop": stop_s})
        assert stop_s not in out["text"]
        assert full.startswith(out["text"])

    # Streaming mode agrees: terminal record marks stopped and the joined
    # deltas equal the final text.
    recs = list(cell.generate_stream({"prompt": "hello", "maxNewTokens": 6,
                                      "stop": [full[1:2]] if len(full) >= 2
                                      else ["zzz"]}))
    final = recs[-1]
    assert "".join(r["text"] for r in recs[:-1]) == final["text"]

    # Validation: bad stop type is a clean 400-class error.
    import pytest as _pytest

    with _pytest.raises(ValueError, match="stop"):
        cell.generate({"prompt": "x", "stop": [42]})


def test_serving_cell_prefix_id_passthrough():
    """`prefixId` flows from the HTTP request shape through to the engine's
    prefix cache (hit visible in /v1/stats)."""
    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None)
    cell.generate({"prompt": "system prompt", "maxNewTokens": 2,
                   "prefixId": "sess"})
    cell.generate({"prompt": "system prompt and more", "maxNewTokens": 2,
                   "prefixId": "sess"})
    pc = cell.stats()["prefixCache"]
    assert pc == {"hits": 1, "misses": 1, "entries": 1}

    import pytest as _pytest

    with _pytest.raises(ValueError, match="prefixId"):
        cell.generate({"prompt": "x", "prefixId": 42})


def test_stream_deltas_survive_split_utf8_codepoint():
    """A multi-byte codepoint split across tokens decodes to U+FFFD until
    its last byte arrives; the stream must hold the provisional tail back
    (never emit a replacement char that will be rewritten) and the joined
    deltas must equal the final text (ADVICE r5, ISSUE 1 satellite)."""
    import threading

    import numpy as np  # noqa: F401 — prompt encoding below

    from kukeon_tpu.runtime.serving_cell import ServingCell

    cell = ServingCell("tiny", num_slots=2, max_seq_len=64,
                       checkpoint=None, dtype=None)

    # Script the engine: "h", then "é" split across two byte tokens, "!".
    script = [0x68] + list("é".encode()) + [0x21]

    class FakeReq:
        def __init__(self):
            self.done = threading.Event()
            self.error = None
            self.cancelled = False
            self.timed_out = False
            self.trace = None

        def cancel(self):
            self.cancelled = True

    class FakeEngine:
        _running = True   # consumer loop reads straight off the queue

        def submit(self, prompt, sp, emit=None, prefix_id=None,
                   deadline_s=None, trace_ctx=None):
            r = FakeReq()
            for i, tok in enumerate(script):
                emit(tok, i == len(script) - 1)
            r.done.set()
            return r

    cell.engine = FakeEngine()
    recs = list(cell.generate_stream({"prompt": "x", "maxNewTokens": 8}))
    final = recs[-1]
    deltas = [r["text"] for r in recs[:-1]]
    assert "".join(deltas) == "hé!" == final["text"]
    assert not any("�" in d for d in deltas)
    # The split codepoint's first byte emitted an empty (held back) delta,
    # completed on the next token.
    assert deltas == ["h", "", "é", "!"]


def test_ndjson_midstream_error_stays_in_band():
    """A generator failure AFTER headers went out must surface as a
    terminal {"error": ...} ndjson line — not as a second interleaved HTTP
    status line corrupting the open stream (ADVICE r5, ISSUE 1 satellite)."""
    import http.client
    import json
    import threading
    from http.server import ThreadingHTTPServer

    from kukeon_tpu.runtime.serving_cell import make_handler

    class BoomCell:
        model_name = "boom"

        def generate(self, req, trace_ctx=None):
            raise AssertionError("non-stream path not under test")

        def generate_stream(self, req, trace_ctx=None):
            yield {"token": 1, "text": "a"}
            yield {"token": 2, "text": "b"}
            raise RuntimeError("device lost mid-stream")

    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(BoomCell()))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1",
                                          server.server_address[1], timeout=10)
        conn.request("POST", "/v1/generate", body=json.dumps({
            "prompt": "x", "stream": True}), headers={
            "Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        raw = resp.read()
        conn.close()
    finally:
        server.shutdown()
        server.server_close()
    assert b"HTTP/" not in raw          # no second status line in the body
    lines = [json.loads(x) for x in raw.decode().splitlines()]
    assert lines[0] == {"token": 1, "text": "a"}
    assert lines[1] == {"token": 2, "text": "b"}
    assert lines[2]["error"].startswith("RuntimeError")
