"""Production inference serving: continuous batching + paged KV + SSE.

`llm_service` registers an `@app.cls` whose container runs ONE shared
decode loop: requests from many clients join and leave the running batch
per step (continuous batching over a paged KV pool — docs/SERVING.md), and
tokens stream back over SSE as they are generated.

    python examples/06_serving.py
"""

import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo checkout

import modal_tpu

app = modal_tpu.App("example-serving")

# real deployments: model="llama3-8b" (or {"name": ..., **overrides}),
# tpu="v5e-1" (the engine runs on one chip; replicas scale out),
# checkpoint=<volume path>, and SLO targets the scheduler scales replicas on
Service = modal_tpu.serving.llm_service(
    app,
    model="tiny",
    max_slots=8,
    name="TinyLLM",
    target_ttft_ms=500,
    target_tokens_per_replica=2000,
)


if __name__ == "__main__":
    with modal_tpu.enable_output(), app.run():
        url = Service.get_web_url(timeout=120)
        print("serving at", url)
        # buffered completion
        body = json.dumps({"text": "hello", "max_new_tokens": 16}).encode()
        req = urllib.request.Request(
            url + "/v1/generate", data=body, headers={"content-type": "application/json"}
        )
        out = json.loads(urllib.request.urlopen(req, timeout=180).read())
        print("tokens:", out["tokens"], f"(TTFT {out['ttft_s']:.3f}s)")
        # streaming: same route with {"stream": true} answers text/event-stream
        # (one `token` event per generated token; see docs/SERVING.md)
