"""The service's wire format, as the benchmark's own copy.

`GetRateLimits` of package `pb.gubernator` (upstream gubernator.proto),
declared here in a private descriptor pool so that the load generators and
the control server import nothing of the program under test (and so no JAX).
"""

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

METHOD = "/pb.gubernator.V1/GetRateLimits"
HEALTH = "/pb.gubernator.V1/HealthCheck"

_T = descriptor_pb2.FieldDescriptorProto


def _field(msg, name, number, ftype, label=_T.LABEL_OPTIONAL, type_name=None):
    f = msg.field.add()
    f.name, f.number, f.type, f.label = name, number, ftype, label
    if type_name:
        f.type_name = type_name


def _build():
    fd = descriptor_pb2.FileDescriptorProto()
    fd.name = "benchmark_gubernator.proto"
    fd.package = "pb.gubernator"
    fd.syntax = "proto3"

    req = fd.message_type.add()
    req.name = "RateLimitReq"
    _field(req, "name", 1, _T.TYPE_STRING)
    _field(req, "unique_key", 2, _T.TYPE_STRING)
    _field(req, "hits", 3, _T.TYPE_INT64)
    _field(req, "limit", 4, _T.TYPE_INT64)
    _field(req, "duration", 5, _T.TYPE_INT64)
    _field(req, "algorithm", 6, _T.TYPE_INT32)
    _field(req, "behavior", 7, _T.TYPE_INT32)

    resp = fd.message_type.add()
    resp.name = "RateLimitResp"
    _field(resp, "status", 1, _T.TYPE_INT32)
    _field(resp, "limit", 2, _T.TYPE_INT64)
    _field(resp, "remaining", 3, _T.TYPE_INT64)
    _field(resp, "reset_time", 4, _T.TYPE_INT64)
    _field(resp, "error", 5, _T.TYPE_STRING)
    entry = resp.nested_type.add()
    entry.name = "MetadataEntry"
    entry.options.map_entry = True
    _field(entry, "key", 1, _T.TYPE_STRING)
    _field(entry, "value", 2, _T.TYPE_STRING)
    _field(resp, "metadata", 6, _T.TYPE_MESSAGE, _T.LABEL_REPEATED,
           ".pb.gubernator.RateLimitResp.MetadataEntry")

    reqs = fd.message_type.add()
    reqs.name = "GetRateLimitsReq"
    _field(reqs, "requests", 1, _T.TYPE_MESSAGE, _T.LABEL_REPEATED,
           ".pb.gubernator.RateLimitReq")
    resps = fd.message_type.add()
    resps.name = "GetRateLimitsResp"
    _field(resps, "responses", 1, _T.TYPE_MESSAGE, _T.LABEL_REPEATED,
           ".pb.gubernator.RateLimitResp")

    hreq = fd.message_type.add()
    hreq.name = "HealthCheckReq"
    hresp = fd.message_type.add()
    hresp.name = "HealthCheckResp"
    _field(hresp, "status", 1, _T.TYPE_STRING)
    _field(hresp, "message", 2, _T.TYPE_STRING)
    _field(hresp, "peer_count", 3, _T.TYPE_INT32)

    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    get = lambda n: message_factory.GetMessageClass(  # noqa: E731
        pool.FindMessageTypeByName("pb.gubernator." + n))
    return {n: get(n) for n in (
        "RateLimitReq", "RateLimitResp", "GetRateLimitsReq",
        "GetRateLimitsResp", "HealthCheckReq", "HealthCheckResp")}


_M = _build()
RateLimitReq = _M["RateLimitReq"]
RateLimitResp = _M["RateLimitResp"]
GetRateLimitsReq = _M["GetRateLimitsReq"]
GetRateLimitsResp = _M["GetRateLimitsResp"]
HealthCheckReq = _M["HealthCheckReq"]
HealthCheckResp = _M["HealthCheckResp"]


def _varint(n):
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def item_bytes(name, unique_key, hits, limit, duration, algorithm, behavior=0):
    """One `requests` entry of a GetRateLimitsReq, tag and length included:
    an RPC's body is the concatenation of its items' bytes."""
    body = RateLimitReq(name=name, unique_key=unique_key, hits=hits,
                        limit=limit, duration=duration, algorithm=algorithm,
                        behavior=behavior).SerializeToString()
    return b"\x0a" + _varint(len(body)) + body
