# Serving image: CPU by default; on TPU hosts the libtpu wheel is present
# via the jax[tpu] extra (install at build time with --build-arg TPU=1).
FROM python:3.12-slim

ARG TPU=0
WORKDIR /app
COPY pyproject.toml README.md ./
COPY parallax_tpu ./parallax_tpu
COPY __graft_entry__.py ./

RUN pip install --no-cache-dir -e . && \
    if [ "$TPU" = "1" ]; then \
      pip install --no-cache-dir "jax[tpu]" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html; \
    else \
      pip install --no-cache-dir "jax[cpu]"; \
    fi && \
    pip install --no-cache-dir aiohttp msgpack safetensors numpy

EXPOSE 8000 3001 3002
# Scheduler by default; workers: `docker run ... join --scheduler-addr ...`
ENTRYPOINT ["python", "-m", "parallax_tpu.cli"]
CMD ["run", "--model-name", "qwen2.5-0.5b", "--min-nodes", "1"]
