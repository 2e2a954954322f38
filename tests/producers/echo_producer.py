"""Fake producer: publishes its parsed handshake back to the consumer.

Mirrors the reference test fixture ``tests/blender/launcher.blend.py:3-9``
(which publishes btid/seed/addresses/remainder for the launcher test to
assert on), but runs headless — no Blender.
"""

import os
import sys
import time

from blendjax.launcher import parse_launch_args
from blendjax.transport import DataPublisherSocket, term_context


def main():
    args, remainder = parse_launch_args(sys.argv)
    pub = DataPublisherSocket(
        args.btsockets["DATA"], btid=args.btid, lingerms=5000
    )
    pub.publish(
        btseed=args.btseed,
        sockets=args.btsockets,
        remainder=remainder,
        jax_platforms=os.environ.get("JAX_PLATFORMS"),
    )
    # Stay alive briefly so the consumer can connect and drain.
    time.sleep(10)
    pub.close()
    term_context()  # guarantee the flush before exiting


if __name__ == "__main__":
    main()
