"""Raw-socket streaming client for the HTTP front-end (docs/http.md).

Speaks the wire protocol by hand — one TCP socket, a hand-written POST,
and incremental SSE parsing — so you can see exactly what travels over
the connection.  Start a server first:

    PYTHONPATH=src python -m repro.launch.serve \
        --arch stablelm-1.6b-smoke --http --port 8000

then:

    python examples/http_client.py --port 8000 --prompt 5,9,13 \
        --max-tokens 16 --temperature 0.0

The prompt is a comma-separated list of token ids (the repo has no real
tokenizer; a plain string also works — the server stub-encodes it).
"""
import argparse
import json
import socket
import sys


def stream_completion(host: str, port: int, body: dict):
    """Yield parsed SSE events for one streamed completion."""
    payload = json.dumps({**body, "stream": True}).encode()
    request = (
        f"POST /v1/completions HTTP/1.1\r\n"
        f"Host: {host}:{port}\r\n"
        f"Content-Type: application/json\r\n"
        f"Content-Length: {len(payload)}\r\n"
        f"Connection: close\r\n"
        f"\r\n"
    ).encode() + payload

    with socket.create_connection((host, port), timeout=300) as sock:
        sock.sendall(request)
        f = sock.makefile("rb")
        status = f.readline().decode().strip()        # HTTP/1.1 200 OK
        if " 200 " not in status + " ":
            rest = f.read().decode(errors="replace")
            raise RuntimeError(f"{status}\n{rest}")
        while f.readline() not in (b"\r\n", b"\n", b""):
            pass                                      # drain headers
        for line in f:
            line = line.rstrip(b"\r\n")
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                return
            yield json.loads(data)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--prompt", default="5,9,13",
                    help="comma-separated token ids, or a plain string")
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0.0 = greedy (deterministic)")
    ap.add_argument("--n", type=int, default=1,
                    help="parallel completions (CoW-forked streams)")
    ap.add_argument("--priority", type=int, default=0)
    args = ap.parse_args()

    try:
        prompt = [int(t) for t in args.prompt.split(",")]
    except ValueError:
        prompt = args.prompt                          # stub-encoded string

    body = {"prompt": prompt, "max_tokens": args.max_tokens,
            "temperature": args.temperature, "n": args.n,
            "priority": args.priority}
    per_choice: dict = {}
    for event in stream_completion(args.host, args.port, body):
        for choice in event["choices"]:
            idx = choice["index"]
            per_choice.setdefault(idx, []).extend(choice["token_ids"])
            if choice["token_ids"]:
                print(f"[{idx}] += {choice['token_ids']}", flush=True)
            if choice["finish_reason"]:
                print(f"[{idx}] finished: {choice['finish_reason']}")
    for idx in sorted(per_choice):
        print(f"choice {idx}: {per_choice[idx]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
