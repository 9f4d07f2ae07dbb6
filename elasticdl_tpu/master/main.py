"""`python -m elasticdl_tpu.master.main` — master process entrypoint
(reference /root/reference/elasticdl/python/master/main.py)."""

import sys

from elasticdl_tpu.common.args import master_parser, validate_args
from elasticdl_tpu.master.master import Master
from elasticdl_tpu.observability import tracing


def main(argv=None, client_started=None):
    """`client_started`: when the `edl` client that runs this master in
    its own process began (the set-up phase `setup.client` runs from
    there to the master's construction)."""
    args = master_parser().parse_args(argv)
    validate_args(args)
    master = Master(args)
    if client_started is not None:
        tracing.record_span(
            "setup.client", client_started,
            master.setup_started - client_started, cat=tracing.SETUP,
        )
    master.prepare()
    return master.run()


if __name__ == "__main__":
    sys.exit(main())
