"""Observability stack tests: StatsListener → StatsStorage → UI server.

Mirrors the reference's TestStatsListener.java / TestStatsStorage.java
(deeplearning4j-ui-parent/deeplearning4j-ui-model/src/test) and the
PlayUIServer attach lifecycle.
"""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import (InputType, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.storage import (FileStatsStorage, InMemoryStatsStorage,
                                        StatsStorageEvent)
from deeplearning4j_tpu.ui import StatsListener, UIServer, dashboard_html
from deeplearning4j_tpu.ui.stats import TYPE_ID


def small_net(seed=42):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Sgd(learning_rate=0.1))
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def toy_data(n=30, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def train_with_listener(storage, iterations=4, **kw):
    net = small_net()
    listener = StatsListener(storage, session_id="sess-1", worker_id="w0", **kw)
    net.set_listeners(listener)
    ds = toy_data()
    for _ in range(iterations):
        net.fit(ds)
    return net, listener


def test_stats_listener_records():
    storage = InMemoryStatsStorage()
    train_with_listener(storage, iterations=4)
    assert storage.list_session_ids() == ["sess-1"]
    assert storage.list_type_ids("sess-1") == [TYPE_ID]
    assert storage.list_worker_ids("sess-1") == ["w0"]
    static = storage.get_static_info("sess-1", TYPE_ID)
    assert static["model"]["class"] == "MultiLayerNetwork"
    assert static["model"]["num_params"] > 0
    assert "0_W" in static["model"]["param_shapes"]
    updates = storage.get_all_updates("sess-1", TYPE_ID)
    assert len(updates) == 4
    last = updates[-1]
    assert last["score"] is not None and np.isfinite(last["score"])
    # per-param stats with histograms
    p = last["parameters"]["0_W"]
    assert set(p) >= {"mean", "stdev", "mean_magnitude", "histogram"}
    assert sum(p["histogram"]["counts"]) == 4 * 8  # 4x8 weight matrix
    # updates (param deltas) exist from the 2nd report on
    assert "updates" in last and "0_W" in last["updates"]
    assert last["update_ratios"]["0_W"] >= 0
    # activations sampled via feed_forward on the stashed minibatch
    assert "activations" in last and len(last["activations"]) == 2
    # performance + memory
    assert last["performance"]["total_examples"] == 4 * 30
    assert last["memory"]["host_rss_bytes"] > 0
    # records are JSON-serializable end to end
    json.dumps(updates)


def test_stats_listener_frequency():
    storage = InMemoryStatsStorage()
    train_with_listener(storage, iterations=6, frequency=2)
    updates = storage.get_all_updates("sess-1", TYPE_ID)
    assert [u["iteration"] for u in updates] == [0, 2, 4]
    # aggregation across skipped iterations still counts every example seen
    # up to the reporting iteration (report at iter 4 = 5 iterations seen)
    assert updates[-1]["performance"]["total_examples"] == 5 * 30


def test_storage_events_and_queries():
    storage = InMemoryStatsStorage()
    events = []
    storage.register_storage_listener(lambda ev: events.append(ev.event_type))
    train_with_listener(storage, iterations=2)
    assert StatsStorageEvent.NEW_SESSION in events
    assert events.count(StatsStorageEvent.POST_UPDATE) == 2
    latest = storage.get_latest_update("sess-1", TYPE_ID)
    assert latest["iteration"] == 1
    after = storage.get_all_updates_after("sess-1", TYPE_ID,
                                          latest["timestamp"] - 1e-4)
    assert after and after[-1]["iteration"] == 1


def test_file_stats_storage_roundtrip(tmp_path):
    path = str(tmp_path / "stats.jsonl")
    storage = FileStatsStorage(path)
    train_with_listener(storage, iterations=3)
    storage.close()
    # reopen: all records reloaded
    re = FileStatsStorage(path)
    assert re.list_session_ids() == ["sess-1"]
    assert re.num_update_records("sess-1", TYPE_ID) == 3
    assert re.get_static_info("sess-1", TYPE_ID)["model"]["num_params"] > 0
    re.close()


def test_file_storage_refresh_live_tail(tmp_path):
    path = str(tmp_path / "s.jsonl")
    reader = FileStatsStorage(path)  # opened before any data exists
    writer = FileStatsStorage(path)  # simulates the training process
    train_with_listener(writer, iterations=2)
    assert reader.num_update_records("sess-1", TYPE_ID) == 0
    assert reader.refresh() == 3  # static + 2 updates appended by writer
    assert reader.num_update_records("sess-1", TYPE_ID) == 2
    assert reader.refresh() == 0  # idempotent
    writer.close()
    reader.close()


def test_ui_server_endpoints():
    storage = InMemoryStatsStorage()
    train_with_listener(storage, iterations=2)
    server = UIServer(port=0).attach(storage)
    try:
        base = f"http://localhost:{server.port}"
        html = urllib.request.urlopen(f"{base}/").read().decode()
        assert "deeplearning4j-tpu training UI" in html
        assert "Score vs iteration" in html
        sessions = json.loads(urllib.request.urlopen(
            f"{base}/api/sessions").read())
        assert sessions == ["sess-1"]
        updates = json.loads(urllib.request.urlopen(
            f"{base}/api/updates?session=sess-1").read())
        assert len(updates) == 2 and updates[-1]["parameters"]
        static = json.loads(urllib.request.urlopen(
            f"{base}/api/static?session=sess-1").read())
        assert static["model"]["class"] == "MultiLayerNetwork"
        assert urllib.request.urlopen(f"{base}/api/sessions").status == 200
    finally:
        server.stop()


def test_dashboard_html_self_contained():
    html = dashboard_html()
    # zero-egress rule: no external scripts/styles/fonts
    assert "http://" not in html.replace("http://localhost", "")
    assert "https://" not in html
    assert "<script src" not in html and "link rel" not in html


# ---------------------------------------------------------------------------
# t-SNE viewer + conv-activations modules (reference TsneModule.java:26,
# ConvolutionalListenerModule.java:32)

def test_tsne_viewer_module():
    server = UIServer(port=0).attach(InMemoryStatsStorage())
    try:
        base = f"http://localhost:{server.port}"
        # in-process upload
        server.upload_tsne("run-a", [[0.0, 1.0], [2.0, 3.0]], labels=["x", "y"])
        # HTTP upload (reference TsneModule POST /tsne/upload)
        body = json.dumps({"session": "run-b",
                           "coords": [[1, 2], [3, 4], [5, 6]]}).encode()
        req = urllib.request.Request(f"{base}/api/tsne/upload", data=body)
        assert json.loads(urllib.request.urlopen(req).read())["n"] == 3
        sessions = json.loads(urllib.request.urlopen(
            f"{base}/api/tsne/sessions").read())
        assert sessions == ["run-a", "run-b"]
        d = json.loads(urllib.request.urlopen(
            f"{base}/api/tsne/data?session=run-a").read())
        assert d["coords"] == [[0.0, 1.0], [2.0, 3.0]]
        assert d["labels"] == ["x", "y"]
        page = urllib.request.urlopen(f"{base}/tsne").read().decode()
        assert "t-SNE viewer" in page and "/api/tsne/sessions" in page
    finally:
        server.stop()


def test_conv_activations_module():
    import base64

    from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
    from deeplearning4j_tpu.nn.conf.layers import OutputLayer
    from deeplearning4j_tpu.optimize.listeners import (
        ConvolutionalIterationListener,
    )
    from deeplearning4j_tpu.optimize.updaters import Adam

    storage = InMemoryStatsStorage()
    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater(Adam(1e-2)).weight_init("relu").list()
            .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    lis = ConvolutionalIterationListener(storage, frequency=1,
                                         session_id="conv-sess")
    net.set_listeners(lis)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 8, 8, 1)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    net.fit(DataSet(x, y), num_epochs=2)

    recs = storage.get_all_updates("conv-sess", "ActivationsListener")
    assert len(recs) == 2
    layers = recs[-1]["layers"]
    assert any("ConvolutionLayer" in k for k in layers)
    png = base64.b64decode(next(iter(layers.values())))
    assert png[:8] == b"\x89PNG\r\n\x1a\n"  # valid PNG magic

    server = UIServer(port=0).attach(storage)
    try:
        base = f"http://localhost:{server.port}"
        sess = json.loads(urllib.request.urlopen(
            f"{base}/api/activations/sessions").read())
        assert sess == ["conv-sess"]
        data = json.loads(urllib.request.urlopen(
            f"{base}/api/activations/data?session=conv-sess").read())
        assert data[-1]["iteration"] == recs[-1]["iteration"]
        page = urllib.request.urlopen(f"{base}/activations").read().decode()
        assert "Convolutional activations" in page
    finally:
        server.stop()


def test_listener_attached_after_fit_reads_nothing():
    """A fit with no reading listener keeps no feature sample, so a listener
    attached afterwards and called by hand finds none: the empty result,
    not an exception."""
    from deeplearning4j_tpu.optimize.listeners import (
        ConvolutionalIterationListener,
    )
    net = small_net()
    net.fit(toy_data())
    assert net._last_features is None
    storage = InMemoryStatsStorage()
    conv = ConvolutionalIterationListener(storage, frequency=1,
                                          session_id="late")
    net.set_listeners(conv)
    conv.iteration_done(net, 0, 0)
    assert conv._conv_activations(net) == {}
    assert storage.get_all_updates("late", "ActivationsListener") == []
    stats = StatsListener(storage, session_id="late")
    stats.iteration_done(net, 0, 0)
    assert stats._sample_activations(net) is None
    assert "activations" not in storage.get_all_updates("late", TYPE_ID)[-1]


def test_inline_js_structural_contract():
    """No JS engine ships in this image, so validate the inline dashboard
    JS structurally: balanced brackets/template-literals outside string
    context, every getElementById target present in the HTML, and every
    fetched /api route actually served (catches renamed ids, route drift,
    and bracket/quote breakage from edits)."""
    import re

    from deeplearning4j_tpu.ui import server as ui_server

    pages = {"dashboard": dashboard_html(),
             "tsne": ui_server._TSNE_HTML,
             "activations": ui_server._ACTIVATIONS_HTML}
    served = ["/api/sessions", "/api/static", "/api/updates", "/api/obs",
              "/api/tsne/sessions", "/api/tsne/data", "/api/tsne/upload",
              "/api/activations/sessions", "/api/activations/data",
              "/remoteReceive"]
    for name, html in pages.items():
        scripts = re.findall(r"<script>(.*?)</script>", html, re.S)
        assert scripts, name
        js = "\n".join(scripts)
        # bracket balance with a tiny string/template scanner
        stack = []
        mode = None  # None | "'" | '"' | "`"
        i = 0
        while i < len(js):
            ch = js[i]
            if mode:
                if ch == "\\":
                    i += 2
                    continue
                if ch == mode:
                    mode = None
                elif mode == "`" and ch == "$" and js[i:i+2] == "${":
                    stack.append("${")
                    mode = None  # back to expression context inside ${...}
                    i += 1
            else:
                if ch in "'\"`":
                    mode = ch
                elif ch in "([{":
                    stack.append(ch)
                elif ch in ")]}":
                    if ch == "}" and stack and stack[-1] == "${":
                        stack.pop()
                        mode = "`"
                    else:
                        opener = {")": "(", "]": "[", "}": "{"}[ch]
                        assert stack and stack[-1] == opener, \
                            f"{name}: unbalanced '{ch}' at {i}"
                        stack.pop()
            i += 1
        assert not stack, f"{name}: unclosed {stack}"
        assert mode is None, f"{name}: unterminated {mode} string"
        # DOM-id contract
        for el_id in set(re.findall(r"\$\(\"([a-zA-Z_]+)\"\)", js)) | \
                set(re.findall(r"getElementById\(\"([a-zA-Z_]+)\"\)", js)):
            assert f'id="{el_id}"' in html or f"id=\"{el_id}\"" in html or \
                js.count(f'id="{el_id}"'), \
                f"{name}: JS references missing DOM id '{el_id}'"
        # route contract
        for route in set(re.findall(r"""fetch\([`"'](/api/[a-z/]+)""", js)) | \
                set(re.findall(r"""j\([`"'](/api/[a-z/]+)""", js)):
            assert route in served, f"{name}: JS fetches unserved {route}"


# ---------------------------------------------------------------------------
# ui-components standalone chart/report library (reference
# deeplearning4j-ui-components Component hierarchy + JSON serde)

def test_ui_components_json_round_trip_and_render():
    from deeplearning4j_tpu.ui.components import (
        ChartHistogram, ChartHorizontalBar, ChartLine, ChartScatter,
        ChartStackedArea, ChartTimeline, ComponentDiv, ComponentTable,
        ComponentText, DecoratorAccordion, Style, component_from_json,
        render_page,
    )

    comps = [
        ChartLine("loss", Style(width=300)).add_series(
            "train", [0, 1, 2, 3], [2.0, 1.2, 0.7, 0.4]).add_series(
            "val", [0, 1, 2, 3], [2.1, 1.5, 1.0, 0.9]),
        ChartScatter("embedding").add_series("pts", [1, 2, 3], [3, 1, 2]),
        ChartHistogram("weights").add_bin(-1, 0, 10).add_bin(0, 1, 30),
        ChartHorizontalBar("per-class F1").add_value("cat", 0.91)
                                          .add_value("dog", 0.84),
        ChartStackedArea("phase time").set_x([0, 1, 2])
            .add_series("fwd", [1, 1.1, 1.0]).add_series("bwd", [2, 2.2, 2.1]),
        ChartTimeline("epochs").add_lane(
            "worker0", [(0.0, 1.0, "e0"), (1.2, 2.0, "e1")]),
        ComponentTable(["metric", "value"]).add_row("accuracy", "0.97"),
        ComponentText("Training summary"),
    ]
    page_comps = [DecoratorAccordion("details", comps[0], comps[6],
                                     default_collapsed=False),
                  ComponentDiv(*comps[1:6]), comps[7]]

    # JSON round trip of EVERY component type preserves structure + render
    for c in comps + page_comps:
        c2 = component_from_json(c.to_json())
        assert type(c2) is type(c)
        assert c2.to_dict() == c.to_dict()
        assert c2.render_html() == c.render_html()

    html = render_page(page_comps, title="run report")
    assert html.startswith("<!DOCTYPE html>")
    assert html.count("<svg") == 6
    assert "per-class F1" in html and "accuracy" in html
    assert "<details open>" in html
    # self-contained: no external refs
    assert "http://" not in html.replace("http://www.w3.org", "")
    # XSS: user strings are escaped
    from deeplearning4j_tpu.ui.components import ComponentText as CT
    assert "<script>" not in CT("<script>alert(1)</script>").render_html()


def test_i18n_messages_and_route():
    """reference DefaultI18N.java: language-keyed messages + fallback."""
    from deeplearning4j_tpu.ui.i18n import DefaultI18N

    i18n = DefaultI18N.get_instance()
    assert i18n is DefaultI18N.get_instance()
    assert i18n.get_message("train.pagetitle") == "Training UI"
    assert i18n.get_message("train.pagetitle", "de") == "Trainings-UI"
    assert i18n.get_message("train.nav.overview", "ja") == "概要"
    # fallback chain: unknown key -> key; unknown lang -> English
    assert i18n.get_message("no.such.key", "de") == "no.such.key"
    assert i18n.get_message("train.pagetitle", "xx") == "Training UI"
    assert set(i18n.languages()) >= {"en", "de", "ja", "zh"}
    i18n.set_default_language("de")
    try:
        assert i18n.get_message("train.session") == "Sitzung"
    finally:
        i18n.set_default_language("en")
    with pytest.raises(ValueError):
        i18n.set_default_language("tlh")

    server = UIServer(port=0).attach(InMemoryStatsStorage())
    try:
        base = f"http://localhost:{server.port}"
        d = json.loads(urllib.request.urlopen(f"{base}/api/i18n?lang=zh").read())
        assert d["messages"]["train.system.memory"] == "内存"
        assert "en" in d["languages"]
    finally:
        server.stop()


def test_i18n_unknown_lang_is_400():
    server = UIServer(port=0).attach(InMemoryStatsStorage())
    try:
        base = f"http://localhost:{server.port}"
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/api/i18n?lang=tlh")
        assert ei.value.code == 400
    finally:
        server.stop()
