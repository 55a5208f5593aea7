"""Distributed root search over an in-process multi-node cluster.

Mirrors the reference's ClusterSandbox tests (multi-node in one process,
scripted failures) at the service level: three searcher nodes, a real
file-backed metastore populated by the indexing pipeline, rendezvous
placement, retry-on-other-node, and the two-phase fetch."""

import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.indexing import IndexingPipeline, PipelineParams, VecSource
from quickwit_tpu.metastore import FileBackedMetastore
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.models.index_metadata import IndexConfig, IndexMetadata, SourceConfig
from quickwit_tpu.query import parse_query_string
from quickwit_tpu.query.ast import MatchAll
from quickwit_tpu.search.models import SearchRequest, SortField
from quickwit_tpu.search.root import RootSearcher, extract_required_tags
from quickwit_tpu.search.service import LocalSearchClient, SearcherContext, SearchService
from quickwit_tpu.storage import RamStorage, StorageResolver

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("ts", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("body", FieldType.TEXT),
        FieldMapping("tenant", FieldType.U64, fast=True),
        FieldMapping("severity", FieldType.TEXT, tokenizer="raw", fast=True),
    ],
    timestamp_field="ts",
    tag_fields=("tenant",),
    default_search_fields=("body",),
)

NUM_DOCS = 600


def make_docs():
    return [{"ts": 1_600_000_000 + i, "body": f"event {i} common word{i % 7}",
             "tenant": i % 3, "severity": ["INFO", "ERROR"][i % 2]}
            for i in range(NUM_DOCS)]


@pytest.fixture(scope="module")
def cluster():
    resolver = StorageResolver.for_test()
    meta_storage = resolver.resolve("ram:///dist/metastore")
    split_uri = "ram:///dist/splits"
    metastore = FileBackedMetastore(meta_storage)
    config = IndexConfig(index_id="logs", index_uri=split_uri, doc_mapper=MAPPER,
                         split_num_docs_target=100)
    metastore.create_index(IndexMetadata(
        index_uid="logs:01", index_config=config,
        sources={"src": SourceConfig("src", "vec")}))
    pipeline = IndexingPipeline(
        PipelineParams(index_uid="logs:01", source_id="src",
                       split_num_docs_target=100, batch_num_docs=50),
        MAPPER, VecSource(make_docs()), metastore,
        resolver.resolve(split_uri))
    pipeline.run_to_completion()

    services = {
        f"node-{i}": SearchService(
            SearcherContext(storage_resolver=resolver), node_id=f"node-{i}")
        for i in range(3)
    }
    clients = {nid: LocalSearchClient(svc) for nid, svc in services.items()}
    root = RootSearcher(metastore, clients)
    return metastore, services, clients, root


def test_distributed_term_search(cluster):
    _, _, _, root = cluster
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("severity:ERROR"),
        max_hits=10, sort_fields=(SortField("ts", "desc"),)))
    assert response.num_hits == NUM_DOCS // 2
    assert len(response.hits) == 10
    # newest ERROR doc first (odd ids are ERROR)
    assert response.hits[0].doc["ts"] == 1_600_000_000 + NUM_DOCS - 1
    assert [h.doc["ts"] for h in response.hits] == sorted(
        (h.doc["ts"] for h in response.hits), reverse=True)


def test_distributed_scored_search_with_offset(cluster):
    _, _, _, root = cluster
    full = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("common", ["body"]),
        max_hits=20))
    paged = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("common", ["body"]),
        max_hits=10, start_offset=10))
    assert [(h.split_id, h.doc_id) for h in paged.hits] == \
        [(h.split_id, h.doc_id) for h in full.hits[10:]]


def test_distributed_aggregations(cluster):
    _, _, _, root = cluster
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("severity:ERROR"),
        max_hits=0,
        aggs={"tenants": {"terms": {"field": "tenant"}}}))
    buckets = {b["key"]: b["doc_count"]
               for b in response.aggregations["tenants"]["buckets"]}
    expected = {}
    for i in range(1, NUM_DOCS, 2):
        expected[i % 3] = expected.get(i % 3, 0) + 1
    assert buckets == expected


def test_time_range_prunes_splits(cluster):
    metastore, services, clients, root = cluster
    # docs are time-ordered, 100/split: querying the first 150 seconds
    # must touch only the first 2 splits
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("*"),
        max_hits=0,
        start_timestamp=1_600_000_000 * 1_000_000,
        end_timestamp=(1_600_000_000 + 150) * 1_000_000))
    assert response.num_hits == 150


def test_tag_pruning_extraction():
    ast = parse_query_string("tenant:2 AND severity:ERROR")
    assert extract_required_tags(ast, ("tenant",)) == {"tenant:2"}
    # disjunctive positions must NOT produce required tags
    ast_or = parse_query_string("tenant:2 OR severity:ERROR")
    assert extract_required_tags(ast_or, ("tenant",)) == set()


def test_index_pattern_resolution(cluster):
    _, _, _, root = cluster
    response = root.search(SearchRequest(
        index_ids=["log*"], query_ast=parse_query_string("*"), max_hits=0))
    assert response.num_hits == NUM_DOCS
    with pytest.raises(ValueError):
        root.search(SearchRequest(index_ids=["nope-*"],
                                  query_ast=parse_query_string("*"), max_hits=0))


def test_search_after_pagination(cluster):
    _, _, _, root = cluster
    page1 = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("*"),
        max_hits=7, sort_fields=(SortField("ts", "desc"),)))
    last = page1.hits[-1]
    # internal sort value for desc sort == raw value
    page2 = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("*"),
        max_hits=7, sort_fields=(SortField("ts", "desc"),),
        search_after=[last.sort_values[0], last.split_id, last.doc_id]))
    ids1 = {(h.split_id, h.doc_id) for h in page1.hits}
    ids2 = {(h.split_id, h.doc_id) for h in page2.hits}
    assert not ids1 & ids2
    assert page2.hits[0].doc["ts"] < page1.hits[-1].doc["ts"] or \
        page2.hits[0].doc["ts"] == page1.hits[-1].doc["ts"]


class FlakyClient:
    """Fails the first leaf_search on each node, then recovers."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def leaf_search(self, request):
        self.calls += 1
        if self.calls == 1:
            raise ConnectionError("injected failure")
        return self.inner.leaf_search(request)

    def fetch_docs(self, request):
        return self.inner.fetch_docs(request)


def test_retry_on_node_failure(cluster):
    metastore, services, clients, _ = cluster
    flaky = {nid: FlakyClient(c) for nid, c in clients.items()}
    # make only ONE node flaky so retries land on healthy nodes
    mixed = dict(clients)
    first = sorted(mixed)[0]
    mixed[first] = flaky[first]
    root = RootSearcher(metastore, mixed)
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("severity:ERROR"),
        max_hits=5))
    assert response.num_hits == NUM_DOCS // 2  # nothing lost despite failure
    assert len(response.hits) == 5


def test_all_snippets(cluster):
    _, _, _, root = cluster
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("common", ["body"]),
        max_hits=3, snippet_fields=("body",)))
    assert response.hits
    for hit in response.hits:
        assert "<em>common</em>" in hit.snippets["body"][0]


def test_split_pruning_short_circuit(cluster):
    """count_hits_exact=False + timestamp sort: splits that cannot beat the
    current top-k are skipped (CanSplitDoBetter short-circuit)."""
    metastore, services, clients, root = cluster
    from quickwit_tpu.search.models import LeafSearchRequest
    from quickwit_tpu.metastore.base import ListSplitsQuery
    from quickwit_tpu.models.split_metadata import SplitState
    from quickwit_tpu.search.models import SplitIdAndFooter

    metadata = metastore.index_metadata("logs")
    splits = metastore.list_splits(ListSplitsQuery(
        index_uids=[metadata.index_uid], states=[SplitState.PUBLISHED]))
    assert len(splits) >= 3
    offsets = [SplitIdAndFooter(
        split_id=s.metadata.split_id,
        storage_uri=metadata.index_config.index_uri,
        num_docs=s.metadata.num_docs,
        time_range=(s.metadata.time_range_start, s.metadata.time_range_end))
        for s in splits]
    service = next(iter(services.values()))
    # fresh context so leaf cache doesn't satisfy everything
    from quickwit_tpu.search.service import SearcherContext, SearchService
    svc = SearchService(SearcherContext(
        storage_resolver=service.context.storage_resolver, batch_size=1))
    request = SearchRequest(
        index_ids=["logs"], query_ast=parse_query_string("*"),
        max_hits=5, sort_fields=(SortField("ts", "desc"),),
        count_hits_exact=False)
    response = svc.leaf_search(LeafSearchRequest(
        search_request=request, index_uid=metadata.index_uid,
        doc_mapping=MAPPER.to_dict(), splits=offsets))
    assert response.resource_stats.get("num_splits_skipped", 0) >= 1
    # correctness: the returned top hits equal the exact-path result
    exact = svc.leaf_search(LeafSearchRequest(
        search_request=SearchRequest(
            index_ids=["logs"], query_ast=parse_query_string("*"),
            max_hits=5, sort_fields=(SortField("ts", "desc"),)),
        index_uid=metadata.index_uid, doc_mapping=MAPPER.to_dict(),
        splits=offsets))
    assert [(h.split_id, h.doc_id) for h in response.partial_hits[:5]] == \
        [(h.split_id, h.doc_id) for h in exact.partial_hits[:5]]


def test_split_pruning_never_skips_on_ties_or_zero_hits(cluster):
    """Regression: ties on the split boundary must not be pruned, and
    max_hits=0 with count_all=false must not crash."""
    metastore, services, clients, root = cluster
    from quickwit_tpu.search.models import LeafSearchRequest, SplitIdAndFooter
    from quickwit_tpu.metastore.base import ListSplitsQuery
    from quickwit_tpu.models.split_metadata import SplitState
    from quickwit_tpu.search.service import SearcherContext, SearchService

    metadata = metastore.index_metadata("logs")
    splits = metastore.list_splits(ListSplitsQuery(
        index_uids=[metadata.index_uid], states=[SplitState.PUBLISHED]))
    offsets = [SplitIdAndFooter(
        split_id=s.metadata.split_id,
        storage_uri=metadata.index_config.index_uri,
        num_docs=s.metadata.num_docs,
        time_range=(s.metadata.time_range_start, s.metadata.time_range_end))
        for s in splits]
    svc = SearchService(SearcherContext(
        storage_resolver=next(iter(services.values())).context.storage_resolver,
        batch_size=1))
    # max_hits=0 + inexact counting: must not crash (IndexError regression)
    response = svc.leaf_search(LeafSearchRequest(
        search_request=SearchRequest(
            index_ids=["logs"], query_ast=parse_query_string("*"),
            max_hits=0, sort_fields=(SortField("ts", "desc"),),
            count_hits_exact=False),
        index_uid=metadata.index_uid, doc_mapping=MAPPER.to_dict(),
        splits=offsets))
    assert response.partial_hits == []


def test_text_field_sort_across_splits():
    """Sorting by a raw text fast field: device top-k by split-local
    ordinal (dictionary is lex-sorted), collector merges the DECODED term
    strings across splits; missing values last in both directions."""
    from quickwit_tpu.serve import Node, NodeConfig
    node = Node(NodeConfig(node_id="txt-node",
                           metastore_uri="ram:///txtsort/metastore",
                           default_index_root_uri="ram:///txtsort/indexes"),
                storage_resolver=StorageResolver.for_test())
    node.index_service.create_index({
        "index_id": "txtsort",
        "doc_mapping": {
            "field_mappings": [
                {"name": "host", "type": "text", "tokenizer": "raw",
                 "fast": True},
                {"name": "body", "type": "text"}],
            "default_search_fields": ["body"]},
        "indexing_settings": {"split_num_docs_target": 3}})
    hosts = ["web-02", "db-01", "web-01", "cache-01", "db-02", None,
             "app-01", "web-03"]
    node.ingest("txtsort", [
        {"host": h, "body": f"tsx doc {i}"} if h else {"body": f"tsx doc {i}"}
        for i, h in enumerate(hosts)])

    def run(order):
        request = SearchRequest(
            index_ids=["txtsort"],
            query_ast=parse_query_string("tsx", ["body"]),
            max_hits=10, sort_fields=[SortField("host", order)])
        response = node.root_searcher.search(request)
        return [h.sort_values[0] if h.sort_values else None
                for h in response.hits]

    present = sorted(h for h in hosts if h)
    assert run("asc") == present + [None]
    assert run("desc") == list(reversed(present)) + [None]

    # rejections are named 400-kind errors, not crashes
    from quickwit_tpu.search.plan import PlanError
    with pytest.raises(Exception) as exc:
        node.root_searcher.search(SearchRequest(
            index_ids=["txtsort"],
            query_ast=parse_query_string("tsx", ["body"]),
            max_hits=2, sort_fields=[SortField("body", "asc")]))
    assert "fast" in str(exc.value)


def test_unsorted_tie_truncation_is_split_order_invariant(monkeypatch):
    """Regression: the batched cross-split merge breaks sort-value ties by
    flattened lane index (parallel/fanout.py:mesh_batch_fn), so the batch lanes
    must be pinned to split_id order no matter how the visit order was
    optimized or recomposed by the offload cut. An unsorted search has
    EVERY hit tied; truncation at max_hits used to keep whichever docs sat
    in the earliest lanes — a different subset cold vs warm (surfaced by
    the DST fanout scenario's cache_cold_equivalence invariant, seed 17)."""
    from quickwit_tpu.serve import Node, NodeConfig
    node = Node(NodeConfig(node_id="tie-node",
                           metastore_uri="ram:///ties/metastore",
                           default_index_root_uri="ram:///ties/indexes"),
                storage_resolver=StorageResolver.for_test())
    node.index_service.create_index({
        "index_id": "ties",
        "doc_mapping": {
            "field_mappings": [{"name": "body", "type": "text"}],
            "default_search_fields": ["body"]},
        "indexing_settings": {"split_num_docs_target": 4}})
    node.ingest("ties", [{"body": f"tied doc {i}"} for i in range(12)])

    request = SearchRequest(
        index_ids=["ties"],
        query_ast=parse_query_string("tied", ["body"]),
        max_hits=6)

    def run(order_fn):
        monkeypatch.setattr(SearchService, "_optimize_split_order",
                            staticmethod(order_fn))
        response = node.root_searcher.search(request)
        return [(h.split_id, h.doc_id) for h in response.hits]

    natural = run(lambda request, splits: list(splits))
    shuffled = run(lambda request, splits: list(reversed(splits)))
    # identical tie subset either way, and it is the prefix of the
    # collector's total order (split_id asc, doc_id asc)
    assert natural == shuffled == sorted(natural)
    assert len(natural) == 6


def test_count_from_metadata_never_opens_split(cluster, monkeypatch):
    """Pure count (match-all, max_hits=0, no aggs): each split's answer is
    its metastore doc count — the leaf must not open the split at all."""
    _, services, _, root = cluster
    # sabotage split opening: any reader access means the fast path failed
    for service in services.values():
        monkeypatch.setattr(
            service.context, "reader",
            lambda split: (_ for _ in ()).throw(
                AssertionError("split opened on a metadata-count query")))
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=MatchAll(), max_hits=0))
    assert response.num_hits == NUM_DOCS
    # a time filter fully covering every split also counts from metadata
    response = root.search(SearchRequest(
        index_ids=["logs"], query_ast=MatchAll(), max_hits=0,
        start_timestamp=0, end_timestamp=10**18))
    assert response.num_hits == NUM_DOCS
    # a partial time filter must fall back to real evaluation -> sabotaged
    failed = root.search(SearchRequest(
        index_ids=["logs"], query_ast=MatchAll(), max_hits=0,
        start_timestamp=(1_600_000_000 + 1) * 1_000_000, end_timestamp=10**18))
    assert failed.num_hits < NUM_DOCS or failed.errors


def test_fanout_over_grpc_framing():
    """Two real nodes with the gRPC plane enabled: the root→leaf
    leaf_search/fetch_docs fan-out rides gRPC framing with binwire
    payloads on a persistent HTTP/2 connection (reference: codegen'd
    SearchService gRPC clients, search.proto:19)."""
    import http.client as hc
    import json as _json

    from quickwit_tpu.config.node_config import NodeConfig
    from quickwit_tpu.serve.grpc_server import GrpcSearchClient
    from quickwit_tpu.serve.node import Node
    from quickwit_tpu.serve.rest import RestServer

    resolver = StorageResolver.for_test()
    nodes, servers = [], []
    for i in range(2):
        node = Node(NodeConfig(node_id=f"g-{i}", rest_port=0, grpc_port=0,
                               metastore_uri="ram:///gfan/ms",
                               default_index_root_uri="ram:///gfan/idx"),
                    storage_resolver=resolver)
        server = RestServer(node)
        server.start()
        nodes.append(node)
        servers.append(server)
    try:
        # mutual membership, gRPC endpoints advertised
        for i, node in enumerate(nodes):
            from quickwit_tpu.serve.http_client import HttpSearchClient
            HttpSearchClient(servers[1 - i].endpoint).heartbeat({
                "node_id": node.config.node_id,
                "roles": list(node.config.roles),
                "rest_endpoint": servers[i].endpoint,
                "grpc_endpoint": node._grpc_advertise()})
        # peers picked the gRPC client
        assert isinstance(nodes[0].clients["g-1"], GrpcSearchClient)
        assert isinstance(nodes[1].clients["g-0"], GrpcSearchClient)

        def rest(port, method, path, body=None):
            conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
            data = (None if body is None else
                    body if isinstance(body, bytes)
                    else _json.dumps(body).encode())
            conn.request(method, path, body=data)
            response = conn.getresponse()
            payload = response.read()
            conn.close()
            return response.status, (_json.loads(payload) if payload else None)

        status, _ = rest(servers[0].port, "POST", "/api/v1/indexes", {
            "index_id": "gfan-logs",
            "doc_mapping": {"field_mappings": [
                {"name": "ts", "type": "datetime", "fast": True,
                 "input_formats": ["unix_timestamp"]},
                {"name": "body", "type": "text"}],
                "timestamp_field": "ts",
                "default_search_fields": ["body"]},
            "indexing_settings": {"split_num_docs_target": 50}})
        assert status == 200
        docs = "\n".join(
            _json.dumps({"ts": 1_600_000_000 + i, "body": f"doc {i} grpcword"})
            for i in range(200)).encode()
        status, result = rest(servers[0].port, "POST",
                              "/api/v1/gfan-logs/ingest", docs)
        assert status == 200 and result["num_ingested_docs"] == 200

        # search via node 1: with 2 searchers the placer fans splits across
        # both, so node 1 must reach node 0's leaf over gRPC (hits + aggs
        # exercise binwire's numpy agg-state path, fetch phase the doc path)
        status, result = rest(
            servers[1].port, "GET",
            "/api/v1/gfan-logs/search?query=grpcword&max_hits=5"
            "&sort_by=-ts")
        assert status == 200 and result["num_hits"] == 200
        assert len(result["hits"]) == 5
        assert result["hits"][0]["ts"] == 1_600_000_199

        status, result = rest(
            servers[1].port, "POST", "/api/v1/gfan-logs/search", {
                "query": "grpcword", "max_hits": 3,
                "aggs": {"by_day": {"date_histogram": {
                    "field": "ts", "fixed_interval": "1d"}}}})
        assert status == 200 and result["num_hits"] == 200
        buckets = result["aggregations"]["by_day"]["buckets"]
        assert sum(b["doc_count"] for b in buckets) == 200
        assert all(h["body"].endswith("grpcword") for h in result["hits"])

        # the persistent channel actually carried traffic
        used = [c for node in nodes for c in node.clients.values()
                if isinstance(c, GrpcSearchClient) and c._channel is not None]
        assert used, "no gRPC channel was used for the fan-out"
    finally:
        for node in nodes:
            if node.grpc_server is not None:
                node.grpc_server.stop()
        for server in servers:
            server.stop()


def test_fanout_over_grpc_framing_under_tls(tmp_path):
    """Round-4 directive #9: a TLS cluster keeps its BINARY plane — the
    gRPC framing runs h2-over-TLS with the cluster cert/CA, peers pick
    the GrpcSearchClient, and distributed search works end to end."""
    import http.client as hc
    import json as _json
    import shutil
    import subprocess

    if shutil.which("openssl") is None:
        pytest.skip("openssl unavailable")
    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True)

    import ssl as _ssl

    from quickwit_tpu.config.node_config import NodeConfig
    from quickwit_tpu.serve.grpc_server import GrpcSearchClient
    from quickwit_tpu.serve.http_client import HttpSearchClient
    from quickwit_tpu.serve.node import Node
    from quickwit_tpu.serve.rest import RestServer

    resolver = StorageResolver.for_test()
    nodes, servers = [], []
    for i in range(2):
        node = Node(NodeConfig(node_id=f"gt-{i}", rest_port=0, grpc_port=0,
                               metastore_uri="ram:///gtls/ms",
                               default_index_root_uri="ram:///gtls/idx",
                               tls_cert_path=str(cert),
                               tls_key_path=str(key),
                               tls_ca_path=str(cert)),
                    storage_resolver=resolver)
        server = RestServer(node)
        server.start()
        nodes.append(node)
        servers.append(server)
    try:
        for i, node in enumerate(nodes):
            # TLS advertise: the gRPC endpoint is published even with TLS on
            assert node._grpc_advertise(), "TLS node must advertise gRPC"
            HttpSearchClient(servers[1 - i].endpoint,
                             **node.config.client_tls_kwargs()).heartbeat({
                "node_id": node.config.node_id,
                "roles": list(node.config.roles),
                "rest_endpoint": servers[i].endpoint,
                "grpc_endpoint": node._grpc_advertise()})
        assert isinstance(nodes[0].clients["gt-1"], GrpcSearchClient)
        assert isinstance(nodes[1].clients["gt-0"], GrpcSearchClient)

        context = _ssl.create_default_context(cafile=str(cert))

        def rest(port, method, path, body=None):
            conn = hc.HTTPSConnection("127.0.0.1", port, timeout=30,
                                      context=context)
            data = (None if body is None else
                    body if isinstance(body, bytes)
                    else _json.dumps(body).encode())
            conn.request(method, path, body=data)
            response = conn.getresponse()
            payload = response.read()
            conn.close()
            return response.status, (_json.loads(payload) if payload else None)

        status, _ = rest(servers[0].port, "POST", "/api/v1/indexes", {
            "index_id": "gtls-logs",
            "doc_mapping": {"field_mappings": [
                {"name": "ts", "type": "datetime", "fast": True,
                 "input_formats": ["unix_timestamp"]},
                {"name": "body", "type": "text"}],
                "timestamp_field": "ts",
                "default_search_fields": ["body"]},
            "indexing_settings": {"split_num_docs_target": 50}})
        assert status == 200
        docs = "\n".join(
            _json.dumps({"ts": 1_600_000_000 + i,
                         "body": f"doc {i} tlsword"})
            for i in range(120)).encode()
        status, result = rest(servers[0].port, "POST",
                              "/api/v1/gtls-logs/ingest", docs)
        assert status == 200 and result["num_ingested_docs"] == 120

        status, result = rest(
            servers[1].port, "GET",
            "/api/v1/gtls-logs/search?query=tlsword&max_hits=5&sort_by=-ts")
        assert status == 200 and result["num_hits"] == 120
        assert len(result["hits"]) == 5

        # a plaintext h2c client must be rejected by the TLS gRPC plane
        from quickwit_tpu.serve.grpc_server import GrpcChannel
        host, port = nodes[0]._grpc_advertise().rsplit(":", 1)
        with pytest.raises(Exception):
            plain = GrpcChannel(host, int(port), timeout=5)
            plain.call("/quickwit.search.SearchService/LeafSearch", b"")

        # the persistent TLS channel actually carried the fan-out
        used = [c for node in nodes for c in node.clients.values()
                if isinstance(c, GrpcSearchClient)
                and c._channel is not None]
        assert used, "no gRPC channel was used for the TLS fan-out"
        assert all(c._channel_ssl is not None for c in used)
    finally:
        for node in nodes:
            if node.grpc_server is not None:
                node.grpc_server.stop()
        for server in servers:
            server.stop()
