"""The real daemon with the timed path broken underneath: one answer in
every fifth GetRateLimits reply is altered where the server produces it."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import serve, wire  # noqa: E402


def plant():
    from gubernator_tpu import server
    real = server.serve_get_rate_limits
    count = [0]

    async def altered(inst, data, context):
        out = await real(inst, data, context)
        count[0] += 1
        if count[0] % 5:
            return out
        msg = wire.GetRateLimitsResp.FromString(out)
        one = msg.responses[0]
        one.remaining = one.remaining + 1 if one.status == 0 else 1
        return msg.SerializeToString()
    server.serve_get_rate_limits = altered


if __name__ == "__main__":
    plant()
    serve.main()
